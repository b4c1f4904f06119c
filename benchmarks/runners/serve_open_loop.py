"""Runner ``serve_open_loop``: a cell served by
``serve.run(build_openai_app(...))`` behind the HTTP proxy, one replica
holding one chip, under open-loop traffic from ``benchmarks.lib.traffic``.

Order of a run: deploy; one warm-up request per prefill bucket (they
compile every program the traffic can reach: the buckets, insert, decode);
idle probes; a seeded sample for the reference; the schedule, whose first
``ramp_seconds`` fill the engine and belong to set-up; the window; the drain
of what the window still owes; and, only where the engine made fewer tokens
than were asked for, every request of the schedule once more, unary, to
learn which of them ended on the tokenizer's EOS.
"""
from __future__ import annotations

import asyncio
import contextlib
import functools
import os
import shutil
import statistics
import time
from typing import List

import numpy as np

from benchmarks.lib import cluster, loadgen, program, traffic


@contextlib.contextmanager
def deployed(config: dict, platform: str, chips: int):
    """``serve.run(build_openai_app(...))`` behind the HTTP proxy on a fresh
    cluster: yields (handle, proxy port, the replica's own device facts)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_openai_app

    cluster.start("serve")
    try:
        handle = serve.run(
            build_openai_app(program.llm_config(
                config, deployment_config=(
                    {"ray_actor_options": {"num_tpus": 1}}
                    if platform == "tpu" else {}))),
            name="llm",
            route_prefix="/v1", _blocking_timeout=600.0)
        port = serve.start_http_proxy()
        info = handle.replica_info.remote().result(timeout=600)
        cluster.require(
            info["platform"] == platform and info["device_count"] == chips,
            f"the replica computes on {info}, the cell needs {chips} "
            f"{platform} device(s)")
        yield handle, port, info
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


async def warm_up(session, url: str, buckets, rng) -> None:
    """One request at the longest prompt of every prefill bucket and a few
    decode ticks: every program the traffic can reach is compiled."""
    for bucket in buckets:
        req = traffic.Request(0.0, _prompt(rng, int(bucket)), int(bucket), 4)
        out = await loadgen.post(
            session, url, loadgen.payload_for(req, {"temperature": 0.0}),
            loadgen.Outcome(req, time.monotonic()))
        cluster.require(out.ok, f"warm-up request failed: {out.error}")


def _prompt(rng, n: int) -> str:
    return "".join(chr(c) for c in rng.integers(97, 123, n))


def _reference(config: dict, jobs):
    """Float32 rows of the plain reference the configuration names, under
    the engine's own initial weights (the engine initialises from
    PRNGKey(0)), on this process's first device, after the cluster released
    the chip. ``jobs`` are (function of ``benchmarks.lib.reference``,
    sequences, length to pad to); one list of rows a job."""
    import jax

    from benchmarks.lib import reference

    out, logits = [], reference.logits_of(config)
    with jax.default_device(jax.devices()[0]):
        params = reference.program_initial_weights(config)
        for fn, sequences, pad_to in jobs:
            toks = np.zeros((len(sequences), pad_to), np.int32)
            for i, s in enumerate(sequences):
                toks[i, :len(s)] = s  # causal: padding cannot reach a token
            out.append(reference.in_blocks(
                functools.partial(getattr(reference, fn), logits), params,
                toks, 1))
    return out


async def _recount(session, url, requests, template, slots):
    """The tokens each request's answer holds, for a run whose engine made
    fewer than were asked for. A streamed answer shows no token (PERF.md
    section 2), so every request is sent once more on the idle engine,
    unary and with ``logprobs: 1``, which lists the tokens: greedy decoding
    of the same prompt makes the same tokens again. An entry is None where
    the answer failed or holds more than ``max_tokens``."""
    gate = asyncio.Semaphore(slots)  # a unary answer may take 60 s in all

    async def again(req):
        async with gate:
            out = await loadgen.post(
                session, url,
                {**loadgen.payload_for(req, template), "stream": False,
                 "logprobs": 1},
                loadgen.Outcome(req, time.monotonic()))
        if not out.ok:
            return None
        # an answer of no token carries no ``logprobs`` at all
        tokens = out.body["choices"][0].get("logprobs", {}).get(
            "tokens", [])
        return ([int(t) for t in tokens]
                if len(tokens) <= req.max_tokens else None)

    return list(await asyncio.gather(*(again(r) for r in requests)))


async def _drive(cell, config, port, handle, *, seed, seconds, trace,
                 started, trace_dir) -> dict:
    import aiohttp

    mix, serve = cell["traffic"], config["serve"]
    loop = asyncio.get_running_loop()
    url = f"http://127.0.0.1:{port}/v1/completions"
    rng = np.random.default_rng(seed)
    unary = {"temperature": 0.0}
    timeout = aiohttp.ClientTimeout(total=float(mix["request_timeout_s"]))
    conn = aiohttp.TCPConnector(limit=0)

    def info():
        return handle.replica_info.remote().result(timeout=300)

    async def one(session, n_prompt, max_tokens, extra=None):
        req = traffic.Request(0.0, _prompt(rng, n_prompt), n_prompt,
                              max_tokens)
        out = loadgen.Outcome(req, time.monotonic())
        return await loadgen.post(
            session, url, {**loadgen.payload_for(req, unary), **(extra or {})},
            out)

    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as s:
        await warm_up(s, url, serve["prefill_buckets"], rng)
        # idle probes: one token each, so the answer IS the first token
        probes = []
        for _ in range(int(mix["idle_probes"])):
            out = await one(s, int(mix["idle_probe_prompt_tokens"]), 1)
            cluster.require(out.ok, f"idle probe failed: {out.error}")
            probes.append((out.done - out.sent) * 1e3)

        # the seeded sample for the reference, on the idle engine, outside
        # the window
        sample = []
        for n_prompt in mix["check_prompt_tokens"]:
            out = await one(s, int(n_prompt), int(mix["check_max_tokens"]),
                            {"logprobs": 1})
            cluster.require(out.ok, f"check request failed: {out.error}")
            sample.append(out)

        # the engine is idle here and again after the drain: its counters
        # between the two are exactly the schedule's requests and tokens
        idle_before = await loop.run_in_executor(None, info)
        ramp = float(mix["ramp_seconds"])
        # two schedules, so that the WINDOW holds the same multiset of
        # sizes and gaps for every seed whatever the ramp drew
        window = traffic.schedule(mix, seed, seconds)
        for r in window:
            r.due_s += ramp
        requests = traffic.schedule(mix, seed ^ 0x5BD1E995, ramp) + window
        t_first = time.monotonic() + 0.05
        t_open, t_close = t_first + ramp, t_first + ramp + seconds
        sender = asyncio.ensure_future(loadgen.open_loop(
            s, url, requests, mix["request"], t_first))
        await asyncio.sleep(max(0.0, t_open - time.monotonic()))
        stats_open = loop.run_in_executor(None, info)
        cpu_open = loop.run_in_executor(None, cluster.node_cpu_seconds)
        profile = None
        if trace:
            t_capture = t_open + capture_start_s(
                [r.due_s - ramp for r in window],
                float(mix["trace_after_seconds"]),
                float(mix["trace_seconds"]), seconds)
            await asyncio.sleep(max(0.0, t_capture - time.monotonic()))
            profile = loop.run_in_executor(
                None, cluster.capture_on_node,
                float(mix["trace_seconds"]), trace_dir)
        await asyncio.sleep(max(0.0, t_close - time.monotonic()))
        stats_close = loop.run_in_executor(None, info)
        cpu_close = loop.run_in_executor(None, cluster.node_cpu_seconds)
        tasks = await sender
        # every request of the schedule is owed an answer: a statistic of
        # the window's requests is one of ALL of them, and the token
        # count below needs the engine idle again
        await asyncio.wait(tasks, timeout=float(mix["drain_seconds"]))
        for t in tasks:
            t.cancel()  # unanswered after the drain: counted as failed
        await asyncio.gather(*tasks, return_exceptions=True)
        everything = [
            t.result() if t.done() and not t.cancelled() else None
            for t in tasks]
        idle_after = await loop.run_in_executor(None, info)
        if profile is not None:
            res = await profile
            cluster.require(res.get("ok"), f"profiler capture failed: {res}")
            captured = [res["started"] - t_open, res["stopped"] - t_open]
            cluster.log({
                "cell": cell["name"], "capture_s_after_open": captured,
                "arrivals_due_in_capture": sum(
                    1 for r in window
                    if captured[0] <= r.due_s - ramp <= captured[1])})

        def delta(a, b):
            return {k: b["engine_stats"][k] - a["engine_stats"][k]
                    for k in b["engine_stats"]}

        # all answered, all admitted, and yet fewer tokens than asked for:
        # an EOS stop, or a fault. Which, only the tokens themselves show.
        in_schedule = delta(idle_before, idle_after)
        recount = in_recount = None
        t_recount = time.monotonic()
        if (all(o is not None and o.ok for o in everything)
                and in_schedule["requests"] == len(requests)
                and _tokens_made(in_schedule) < sum(
                    r.max_tokens for r in requests)):
            recount = await _recount(
                s, url, requests, mix["request"],
                int(serve["max_batch_slots"]))
            in_recount = delta(
                idle_after, await loop.run_in_executor(None, info))

    return {"probes": probes, "everything": everything,
            "outcomes": everything[len(requests) - len(window):],
            "t_close": t_close,
            "engine_schedule": in_schedule,
            "recount": recount, "engine_recount": in_recount,
            "recount_s": time.monotonic() - t_recount,
            "engine_window": delta(await stats_open, await stats_close),
            "node_cpu_s": await cpu_close - await cpu_open,
            "sample": sample, "setup_s": t_open - started}


def capture_start_s(due, after_s: float, span_s: float,
                    window_s: float) -> float:
    """Seconds after the window opens at which the profiler's capture is
    asked for: ``after_s``, or as much later as it takes for a request of
    ``due`` (seconds after the opening) to arrive inside the capture, a
    quarter of its ``span_s`` or more from either end.

    The readers of ``engine.admit`` need an admission in every seed's
    trace, and an admission follows an arrival. A capture at a fixed place
    has none in one seed of fourteen at 0.6 requests/s over 4 s (the
    stratified gaps reach 6.9 s), and the driver's check of PR 26 was
    refused for a traced line without ``engine.admit_stall_ms``. Two seeds
    of three are captured where they were, the others up to 5 s later."""
    edge = span_s / 4
    for t in sorted(due):
        start = max(after_s, t - (span_s - edge))
        if t >= start + edge and start + span_s <= window_s:
            return start
    return after_s  # no arrival to wait for


def _tokens_made(counters: dict) -> int:
    """The first token of a request is its prefill's: counted under
    ``requests``, not under ``tokens_generated``."""
    return counters["requests"] + counters["tokens_generated"]


def tokens_per_request(asked, in_schedule, recount, in_recount):
    """(ok, tokens the engine made for each request) of a schedule.

    A streamed answer carries no count a client can read (PERF.md section
    2), so the engine's own counters are held against what was asked for,
    between two moments at which the engine was idle. ``asked`` is each
    request's ``max_tokens``, None for one that got no answer;
    ``in_schedule`` the engine's counters over the schedule. Every request
    was admitted, and together they made exactly the tokens asked for: a
    request never makes more than its ``max_tokens``, so the sums agree
    only if every single request made all of its own, and ``max_tokens`` IS
    then the count of tokens made, by which the per-token time divides.

    Where the engine made fewer, ``recount`` (``_recount``) holds the
    tokens of each request's answer. The engine ends an answer before its
    ``max_tokens`` only on EOS, which it makes and counts and then cuts off
    the answer (``llm/engine.py:448``): a short answer of n tokens stands
    for n + 1 made. The sum has to be what the engine made under the
    schedule, and what it made again under the recount (``in_recount``).
    That the token after a short answer WAS EOS, the reference decides
    (``run``). A request cut short in any other way (an early end of a
    stream, a dropped token) fails the run, and so does a shortfall that no
    recount explains."""
    made = _tokens_made(in_schedule)
    ok = None not in asked and in_schedule["requests"] == len(asked)
    if ok and made == sum(asked):
        return True, list(asked)
    if ok and recount is not None and None not in recount:
        each = [len(got) + (len(got) < n) for got, n in zip(recount, asked)]
        if (sum(each) == made and in_recount["requests"] == len(recount)
                and _tokens_made(in_recount) == made):
            return True, each
    return False, list(asked)


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
        "idle_ms_per_token_of_the_check_requests": [
            (o.done - o.sent) * 1e3
            / max(1, len((o.body["choices"][0].get("logprobs") or {}).get(
                "tokens", [])))
            for o in got["sample"]],
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

    sequences, served = [], []
    eos = int(config["serve"]["eos_token_id"])
    for o in got["sample"]:
        # an answer of no token (EOS at once) carries no ``logprobs``
        lp = o.body["choices"][0].get("logprobs") or {
            "tokens": [], "token_logprobs": []}
        sequences.append(ids(o.request.prompt)
                         + [int(t) for t in lp["tokens"]])
        served.append((o.request.prompt_tokens, lp["token_logprobs"]))
    # an answer that ended before its ``max_tokens`` (a request of the
    # recount, or one of the sample): the reference has to find EOS the
    # likeliest token after it, within the tolerance
    ended = [ids(o.request.prompt) + list(tokens) + [eos]
             for o, tokens in zip(everything, got["recount"] or [])
             if o is not None and tokens is not None
             and len(tokens) < o.request.max_tokens]
    ended += [s + [eos] for s, o in zip(sequences, got["sample"])
              if len(s) - o.request.prompt_tokens < o.request.max_tokens]
    jobs = [("token_logprobs", sequences, int(mix["check_pad_to"]))]
    if ended:
        jobs.append(("greedy_gaps", ended, int(mix["context_limit"])))
    rows = _reference(config, jobs)
    worst = 0.0
    for (n_prompt, got_lp), row in zip(served, rows[0]):
        want = row[n_prompt - 1:n_prompt - 1 + len(got_lp)]
        worst = max([worst, *np.abs(np.asarray(got_lp) - want).tolist()])
    tol = float(mix["logprob_tolerance"])
    # the gap at the EOS a sequence ends on (its token len - 1, row len - 2)
    eos_gaps = [float(row[len(s) - 2])
                for s, row in zip(ended, rows[1] if ended else [])]
    cluster.log({"cell": cell["name"], "check_sequences": len(sequences),
                 "max_abs_logprob_diff": worst, "tolerance": tol,
                 "token_counts_ok": counts_ok,
                 "answers_ended_early": len(ended),
                 "eos_under_the_reference_choice_by": eos_gaps})
    outputs_ok = worst <= tol and all(g <= tol for g in eos_gaps)

    return {
        "correct": bool(outputs_ok and counts_ok and failed == 0),
        "attempted": len(outcomes), "failed": failed,
        "end_to_end": end_to_end,
        "device": {"platform": info["platform"], "kind": info["device_kind"],
                   "count": int(info["device_count"]),
                   "memory_peak_bytes": cluster.memory_peak_bytes(memory)},
        "trace_dir": trace_dir if trace else None,
        "facts": {"decode_program": mix["decode_program"],
                  "device_kind": info["device_kind"], "chips": chips},
    }
