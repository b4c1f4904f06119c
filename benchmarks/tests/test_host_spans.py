"""The readers of the program's own spans, on a small trace recorded on a
v5e chip with the spans in it (``data/v5e_1chip_spans.xplane.pb``,
``record_hot_path_trace.py``): every value is computed a second time here
from the raw events, by arithmetic written out, and the chip's clock is
shown to lie a constant behind the host's, by what the runtime's own enqueue
events say."""
import os
import statistics

import pytest

from benchmarks import run
from benchmarks.lib import host_spans
from benchmarks.lib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
WITH_SPANS = os.path.join(DATA, "v5e_1chip_spans.xplane.pb")
WITHOUT_SPANS = os.path.join(DATA, "v5e_1chip.xplane.pb")  # PR 23's
FACTS = {"decode_program": "jit_decode", "train_program": "jit_step_fn"}
SPAN_READERS = ["engine.tick_sample_ms", "engine.tick_fetch_ms",
                "engine.admit_stall_ms", "train.report_ms", "train.input_ms",
                "trace.idle_unattributed_share.train",
                "trace.idle_unattributed_share.serve"]


def _read(monkeypatch, name, path):
    monkeypatch.setattr(host_spans, "TRACE_ROOT", path)
    return run.read_layer_metric(name, T.load(path), FACTS)


@pytest.fixture(scope="module")
def raw():
    """(thread line number, name, start, end) of every program span and
    (name, start, end) of chip 0's programs and operations, straight from
    the file, the chip's times moved onto the host's clock by ``offset``:
    the most that any program's execution reads before its own enqueue."""
    from jax.profiler import ProfileData

    spans, programs, ops, enqueued, started = [], [], [], {}, {}
    for plane in ProfileData.from_file(WITH_SPANS).planes:
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                start, end = int(ev.start_ns), int(ev.start_ns) + int(
                    ev.duration_ns)
                if plane.name == "/host:CPU" and ev.name.startswith(
                        ("engine.", "train.", "llm.")):
                    spans.append((n, ev.name, start, end))
                elif plane.name == "/host:CPU" and (
                        ev.name == "DoEnqueueProgram"):
                    enqueued[dict(ev.stats)["run_id"]] = start
                elif plane.name == "/device:TPU:0":
                    if line.name == "XLA Modules":
                        programs.append((ev.name, start, end))
                        started[dict(ev.stats)["run_id"]] = start
                    elif line.name == "XLA Ops":
                        ops.append((ev.name, start, end))
    assert len(set(enqueued) & set(started)) >= 20
    offset = max(enqueued[r] - started[r] for r in enqueued if r in started)
    on_host = lambda evs: sorted(  # noqa: E731
        ((n, s + offset, e + offset) for n, s, e in evs), key=lambda x: x[1])
    return {"spans": spans, "programs": on_host(programs),
            "ops": on_host(ops), "offset": offset}


def _durations_ms(raw, name):
    return [(end - start) / 1e6 for _, n, start, end in raw["spans"]
            if n == name]


@pytest.mark.parametrize("metric,span", [
    ("engine.tick_sample_ms", "engine.tick.sample"),
    ("engine.admit_stall_ms", "engine.admit"),
    ("train.report_ms", "train.report"),
    ("train.input_ms", "train.next_batch"),
])
def test_a_mean_duration_is_the_mean_of_the_raw_events(
        monkeypatch, raw, metric, span):
    durations = _durations_ms(raw, span)
    assert len(durations) >= 3
    assert _read(monkeypatch, metric, WITH_SPANS) == pytest.approx(
        sum(durations) / len(durations), rel=1e-9)


def _ticks_and_decodes(raw):
    """(tick start, tick end, fetch end, decode start, decode end) for
    every tick with a decode program that began inside it."""
    decodes = [(s, e) for n, s, e in raw["programs"]
               if n.startswith("jit_decode(")]
    out = []
    for line, name, start, end in raw["spans"]:
        if name != "engine.tick":
            continue
        inside = [(s, e) for s, e in decodes if start <= s < end]
        fetch = [e for l, n, s, e in raw["spans"] if l == line
                 and n == "engine.tick.fetch" and start <= s and e <= end]
        if inside and fetch:
            out.append((start, end, fetch[0], *inside[0]))
    return decodes, out


def test_the_chips_clock_lies_a_constant_behind_the_hosts(raw):
    # the fact the readers correct for: the chip's events read some tenths
    # of a millisecond before the host events that caused them (0.32 ms in
    # this recording, 0.59-1.74 ms in six other captures of PR 24)
    assert 0.1e6 < raw["offset"] < 3e6
    assert host_spans.load(WITH_SPANS).device_clock_offset_ns == raw["offset"]
    decodes, ticks = _ticks_and_decodes(raw)
    assert len(decodes) >= 6
    # moved by that one constant, every decode program on chip 0 starts
    # inside a tick of the engine...
    assert len(ticks) == len(decodes)
    assert len({t[3] for t in ticks}) == len(decodes)
    dispatches = {s: e for _, n, s, e in raw["spans"]
                  if n == "engine.tick.dispatch"}
    for start, end, fetch_end, dec_start, dec_end in ticks:
        # ...after the tick began to dispatch it, and is over before the
        # host has its logits, or at most half a millisecond after
        dispatch = min(s for s in dispatches if s >= start)
        assert dispatch <= dec_start < dec_end <= fetch_end + 500_000
    # and every train step's program lies inside its ``train.step``
    steps = [(s, e) for _, n, s, e in raw["spans"] if n == "train.step"]
    programs = [(s, e) for n, s, e in raw["programs"]
                if n.startswith("jit_step_fn(")]
    assert len(steps) == len(programs) == 4
    fetches = sorted((s, e) for _, n, s, e in raw["spans"]
                     if n == "train.loss_fetch")
    for (s0, s1), (p0, p1), (_, f1) in zip(sorted(steps), programs, fetches):
        assert s0 < p0 < p1 <= f1 + 500_000 and f1 < s1


def test_tick_fetch_is_host_end_less_device_end(monkeypatch, raw):
    _, ticks = _ticks_and_decodes(raw)
    waits = [(fetch_end - dec_end) / 1e6
             for _, _, fetch_end, _, dec_end in ticks]
    got = _read(monkeypatch, "engine.tick_fetch_ms", WITH_SPANS)
    assert got == pytest.approx(statistics.fmean(waits), rel=1e-9)
    assert 0 < got < 2  # the logits of a toy model


def test_unattributed_idle_share_by_brute_force(monkeypatch, raw):
    # idle stretches of chip 0's op line
    gaps, end = [], raw["ops"][0][2]
    for _, s, e in raw["ops"][1:]:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    # the loop thread (here the engine's: 20 ticks against 4 steps), and
    # its spans that hold no other
    counts = {}
    for line, name, _, _ in raw["spans"]:
        if name in ("engine.tick", "train.step"):
            counts[line] = counts.get(line, 0) + 1
    loop = max(counts, key=counts.get)
    mine = [(s, e) for line, _, s, e in raw["spans"] if line == loop]
    # only while that thread was on record: a span open when the capture
    # began is not in it
    first, last = min(s for s, _ in mine), max(e for _, e in mine)
    gaps = [(max(lo, first), min(hi, last)) for lo, hi in gaps
            if lo < last and hi > first]
    idle = sum(hi - lo for lo, hi in gaps)
    leaf = [(s, e) for s, e in mine if not any(
        (s2, e2) != (s, e) and s <= s2 and e2 <= e for s2, e2 in mine)]
    # leaves of one thread never overlap: plain sums
    under = sum(max(0, min(hi, e) - max(lo, s))
                for lo, hi in gaps for s, e in leaf)
    share = 100.0 * (idle - under) / idle
    assert 0 <= share <= 100
    # one quantity under two names
    for metric in ("trace.idle_unattributed_share.serve",
                   "trace.idle_unattributed_share.train"):
        assert _read(monkeypatch, metric, WITH_SPANS) == pytest.approx(
            share, rel=1e-9)


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_no_span_no_number(monkeypatch, metric):
    """A trace of a program without the spans (the parent commit's): the
    reader returns None, the line leaves the metric out, nothing raises."""
    assert host_spans.load(WITHOUT_SPANS) is None
    assert _read(monkeypatch, metric, WITHOUT_SPANS) is None
    # and where there is no trace at all
    monkeypatch.setattr(host_spans, "TRACE_ROOT", os.path.join(DATA, "none"))
    assert run.read_layer_metric(metric, None, FACTS) is None


def test_spans_carry_their_arguments():
    spans = host_spans.load(WITH_SPANS)
    admits = spans.named("engine.admit")
    assert len(admits) == 4
    assert all(set(a.args) >= {"rid", "queued_ms", "prompt_tokens", "bucket",
                               "prefix", "slot"} for a in admits)
    assert len({a.args["rid"] for a in admits}) == 4
    finishes = {f.args["rid"]: f for f in spans.named("engine.finish")}
    assert set(finishes) == {a.args["rid"] for a in admits}
    assert all(t.args["compiled"] == 0 for t in spans.named("engine.tick"))
    assert [s.args["step_num"] for s in spans.named("train.step")] == [
        0, 1, 2, 3]


def test_benchmark_json_lists_the_new_readers():
    import json

    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in SPAN_READERS:
        assert os.path.isfile(os.path.join(
            run.BENCH_DIR, "layer_metrics", name + ".py"))
        assert entries[name]["source"] == "program_span"
    # appended: what was there stands first, as it was
    assert [m["name"] for m in bench["per_layer"]][-len(SPAN_READERS):] == (
        SPAN_READERS)
