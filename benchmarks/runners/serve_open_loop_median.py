"""Runner ``serve_open_loop_median``: ``serve_open_loop`` with a comparison
that a routing flip does not decide.

Deployment, warm-up, probes, the check requests, schedule, window, drain,
recount and the token count are ``serve_open_loop``'s own functions. What
differs is how the check requests' log-probabilities are held against the
plain reference:

- three limits, not one. ``logprob_median_tolerance`` bounds the MEDIAN of
  |served - reference| over all the check requests' answer tokens;
  ``logprob_request_median_tolerance`` the median over each single
  request's; ``logprob_tolerance`` still bounds the single largest gap (and
  EOS under the reference's own greedy choice). Why: a router whose k gates
  are near 1/k each (sigmoid scores, renormalised) puts the k-th and the
  k+1-th expert in the other order in a few token-layers of a hundred under
  bf16's own rounding, and the chosen token's log-probability then moves by
  0.1 to 0.6: every sound run holds such tokens, so its largest gap is
  theirs, and a precision below the configuration's adds more of the same
  and no floor under them. The median token is one that did not flip: it
  reads bf16's rounding in a sound run and rises with a fault that touches
  most tokens (a lower precision, a position signal where there is none).
  A fault that only one request can meet (a window ignored, a ring that
  wraps wrongly: the long prompt's) moves that request's median and not the
  median of all. The largest gap keeps watch for one token far off.
- the reference's log-probability of a chosen token is its logit less the
  row's log-sum-exp: one ``[T, V]`` array on the chip beside the weights,
  where ``lib/reference.py:token_logprobs`` holds two (the logits and
  their log-softmax: 2 x 4.1 GB at 5120 x 200192).

``run`` repeats ``serve_open_loop.run`` line for line up to the comparison,
which that function holds inline: a PR that adds a cell edits no file of the
benchmark, so the statistic could not be given to it there (PERF.md section
7 asks a ``benchmark`` PR to fold the two runners into one).
"""
from __future__ import annotations

import asyncio
import os
import shutil
import statistics

import numpy as np

from benchmarks.lib import cluster, loadgen
from benchmarks.runners.serve_open_loop import (
    _drive,
    _tokens_made,
    deployed,
    tokens_per_request,
)


def chosen_logprobs(logits, params, tokens):
    """log p(tokens[:, t+1] | tokens[:, :t+1]) for every t: [B, T-1]."""
    import jax
    import jax.numpy as jnp

    z = logits(params, tokens[:, :-1])
    chosen = jnp.take_along_axis(z, tokens[:, 1:, None], axis=-1)[..., 0]
    return chosen - jax.nn.logsumexp(z, axis=-1)


def greedy_gaps(logits, params, tokens):
    """How far tokens[:, t+1] lies under the reference's own greedy choice
    after tokens[:, :t+1], in log-probability (the log-sum-exp cancels):
    [B, T-1]; 0 where it IS that choice."""
    import jax.numpy as jnp

    z = logits(params, tokens[:, :-1])
    chosen = jnp.take_along_axis(z, tokens[:, 1:, None], axis=-1)[..., 0]
    return z.max(axis=-1) - chosen


def reference_rows(logits, params, fn, sequences, pad_to: int):
    """``fn(logits, params, one padded sequence)`` for every sequence, a
    row each, at precision ``highest`` (``lib/reference.py:in_blocks``)."""
    import functools

    from benchmarks.lib import reference

    toks = np.zeros((len(sequences), pad_to), np.int32)
    for i, s in enumerate(sequences):
        toks[i, :len(s)] = s  # causal: padding cannot reach a token
    return reference.in_blocks(functools.partial(fn, logits), params, toks, 1)


def answer_gaps(logits, params, sample, pad_to: int):
    """|served - reference| at every answer token of every check request:
    one array a request. ``sample`` holds (prompt ids, answer ids, served
    log-probabilities) a request; the reference reads prompt + answer in
    one full forward."""
    rows = reference_rows(logits, params, chosen_logprobs,
                          [p + a for p, a, _ in sample], pad_to)
    return [np.abs(np.asarray(lp) - row[len(p) - 1:len(p) - 1 + len(lp)])
            for (p, _, lp), row in zip(sample, rows)]


def readings(gaps) -> dict:
    """What the three limits are held against: the largest gap, the median
    gap of all answer tokens, and each request's median gap."""
    gaps = [g for g in gaps if len(g)]
    return {
        "max_abs_logprob_diff": max([0.0] + [float(g.max()) for g in gaps]),
        "median_abs_logprob_diff": float(
            np.median(np.concatenate(gaps))) if gaps else 0.0,
        "request_median_abs_logprob_diff": [
            float(np.median(g)) for g in gaps]}


def within(read: dict, eos_gaps, mix: dict) -> bool:
    largest = float(mix["logprob_tolerance"])
    return bool(
        read["max_abs_logprob_diff"] <= largest
        and read["median_abs_logprob_diff"]
        <= float(mix["logprob_median_tolerance"])
        and all(m <= float(mix["logprob_request_median_tolerance"])
                for m in read["request_median_abs_logprob_diff"])
        and all(g <= largest for g in eos_gaps))


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace: bool,
        platform: str, chips: int, started: float) -> dict:
    mix = cell["traffic"]
    trace_dir = os.path.join(cluster.WORK_DIR, "trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    with deployed(config, platform, chips) as (handle, port, info):
        got = asyncio.run(_drive(
            cell, config, port, handle, seed=seed, seconds=seconds,
            trace=trace, started=started, trace_dir=trace_dir))
        memory = cluster.node_memory_stats()

    outcomes, everything = got["outcomes"], got["everything"]
    done = [o for o in outcomes if o is not None and o.ok]
    # below the knee every request of the schedule is owed an answer
    failed = sum(1 for o in outcomes if o is None or not o.ok)
    cluster.require(bool(done), "no request of the window was answered")

    schedule = got["engine_schedule"]
    asked = [o.request.max_tokens if o is not None else None
             for o in everything]
    counts_ok, tokens = tokens_per_request(
        asked, schedule, got["recount"], got["engine_recount"])
    for o, n in zip(everything, tokens):
        if o is not None:
            o.tokens = n
    tokens_asked = sum(n for n in asked if n is not None)
    tokens_made = _tokens_made(schedule)
    eos_stops = sum(1 for o in everything
                    if o is not None and o.tokens < o.request.max_tokens)

    latency_ms = [(o.done - o.due) * 1e3 for o in done]
    per_token_ms = [l / o.tokens for l, o in zip(latency_ms, done)]
    late_ms = [(o.sent - o.due) * 1e3 for o in outcomes if o is not None]
    p = loadgen.percentile
    end_to_end = {"setup_s": got["setup_s"],
                  "per_token_p50_ms": p(per_token_ms, 50)}
    in_window = got["engine_window"]
    ticks = max(in_window["ticks"], 1)
    cluster.log({
        "cell": cell["name"], "attempted": len(outcomes),
        "answered": len(done), "failed": failed,
        "answered_in_window": sum(
            1 for o in done if o.done <= got["t_close"]),
        # unjudged: the tails swing with who meets whom (PERF.md section 6)
        "request_ms_p50_p75_p90_p95": [p(latency_ms, q) for q in (50, 75, 90, 95)],
        "per_token_ms_p50_p75_p90_p95": [
            p(per_token_ms, q) for q in (50, 75, 90, 95)],
        "generator_late_ms_p50_p95_max": [
            p(late_ms, 50), p(late_ms, 95), max(late_ms)],
        "idle_probe_ms_median": statistics.median(got["probes"]),
        # where a far-off run lost its time: the tick loop (ms a tick), its
        # occupancy (tokens a tick) or threads of the replica's process
        # that span (its CPU seconds a second of the window)
        "window_ms_per_tick": seconds * 1e3 / ticks,
        "window_tokens_per_tick": in_window["tokens_generated"] / ticks,
        "window_requests_admitted": in_window["requests"],
        "node_cpu_s_per_s": got["node_cpu_s"] / seconds,
        "schedule_requests_sent_admitted": [
            len(everything), schedule["requests"]],
        "schedule_tokens_asked_made": [tokens_asked, tokens_made],
        "schedule_requests_ended_on_eos": eos_stops,
        # only a run whose engine made fewer tokens than asked recounts
        "recount_answers_tokens_seconds": None if got["recount"] is None else [
            sum(1 for r in got["recount"] if r is not None),
            sum(len(r) for r in got["recount"] if r is not None),
            got["recount_s"]],
        "errors": sorted({o.error for o in outcomes
                          if o is not None and o.error})[:5],
    })

    # correctness: the sample's chosen tokens, prefill then cached decode,
    # against the reference's full forward over prompt + output
    def ids(text):
        return [b + 2 for b in text.encode()]  # the byte tokenizer's

    sample = []
    eos = int(config["serve"]["eos_token_id"])
    for o in got["sample"]:
        # an answer of no token (EOS at once) carries no ``logprobs``
        lp = o.body["choices"][0].get("logprobs") or {
            "tokens": [], "token_logprobs": []}
        sample.append((ids(o.request.prompt), [int(t) for t in lp["tokens"]],
                       lp["token_logprobs"]))
    # an answer that ended before its ``max_tokens`` (a request of the
    # recount, or one of the sample): the reference has to find EOS the
    # likeliest token after it, within the tolerance
    ended = [ids(o.request.prompt) + list(tokens) + [eos]
             for o, tokens in zip(everything, got["recount"] or [])
             if o is not None and tokens is not None
             and len(tokens) < o.request.max_tokens]
    ended += [prompt + answer + [eos]
              for (prompt, answer, _), o in zip(sample, got["sample"])
              if len(answer) < o.request.max_tokens]

    import jax

    from benchmarks.lib import reference

    # on this process's first device, after the cluster released the chip,
    # under the engine's own initial weights
    logits = reference.logits_of(config)
    with jax.default_device(jax.devices()[0]):
        params = reference.program_initial_weights(config)
        read = readings(answer_gaps(
            logits, params, sample, int(mix["check_pad_to"])))
        # the gap at the EOS a sequence ends on (its token len - 1, row
        # len - 2)
        eos_gaps = [float(row[len(s) - 2]) for s, row in zip(
            ended, reference_rows(logits, params, greedy_gaps, ended,
                                  int(mix["context_limit"]))
            if ended else [])]
    cluster.log({"cell": cell["name"], "check_sequences": len(sample), **read,
                 "tolerance": float(mix["logprob_tolerance"]),
                 "median_tolerance": float(mix["logprob_median_tolerance"]),
                 "request_median_tolerance": float(
                     mix["logprob_request_median_tolerance"]),
                 "token_counts_ok": counts_ok,
                 "answers_ended_early": len(ended),
                 "eos_under_the_reference_choice_by": eos_gaps})

    return {
        "correct": bool(
            within(read, eos_gaps, mix) and counts_ok and failed == 0),
        "attempted": len(outcomes), "failed": failed,
        "end_to_end": end_to_end,
        "device": {"platform": info["platform"], "kind": info["device_kind"],
                   "count": int(info["device_count"]),
                   "memory_peak_bytes": cluster.memory_peak_bytes(memory)},
        "trace_dir": trace_dir if trace else None,
        "facts": {"decode_program": mix["decode_program"],
                  "device_kind": info["device_kind"], "chips": chips},
    }
