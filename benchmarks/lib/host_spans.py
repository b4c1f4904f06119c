"""The program's own host spans, read from the profiler trace the device
metrics come from.

``llm/engine.py``, ``llm/serving.py`` and ``train/trainer.py`` mark what
their loops do with ``jax.profiler.TraceAnnotation``: with a capture running
the spans land on the trace's host plane (``/host:CPU``), one line per
thread. A span's keyword arguments come back as the event's stats. A trace
of a program without the spans (the commits before PR 24) gives no span, and
every reader built on this returns ``None``.

The host plane and the chips' planes share a timeline, not a clock: on the
v5e the chip's ``XLA Modules`` events read 0.3-2.1 ms EARLIER than the host
events that caused them, another amount in every capture (eight captures,
my chip runs, PR 24; the recorded one: ``benchmarks/tests/
test_host_spans.py``). The trace itself says by how
much: the runtime's ``DoEnqueueProgram`` host event and the program's
execution on the chip carry the same ``run_id``, and no program starts
before its enqueue did. ``HostSpans.device_clock_offset_ns`` is the least
shift of the chip's times that makes that true for every program of the
capture: a lower bound on the true offset, short of it by the capture's
smallest launch latency. Every reader that compares a span with a device
event adds it to the device's times first.

A per-layer reader gets the reduced device trace and no path, so ``load()``
takes the newest ``.xplane.pb`` under the benchmark's trace directory: the
file ``run.py`` has just reduced (a runner empties its cell's directory
before the run).

``python -m benchmarks.lib.host_spans <trace dir or file>`` prints the
lines, the spans' counts and means, and what filled chip 0's longest idle
gaps, for reading a trace by hand.
"""
from __future__ import annotations

import functools
import os
import statistics
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.lib import cluster
from benchmarks.lib import trace as T

HOST_PLANE = "/host:CPU"
CHIP0_PLANE = "/device:TPU:0"
ENQUEUE = "DoEnqueueProgram"  # the runtime's host event; has the run_id
# the names the program gives its spans; everything else on the host plane
# is the runtime's own (PjitFunction, ExecuteHelper, ...)
PROGRAM_SPAN = ("engine.", "llm.", "train.")
LOOP_SPAN = ("engine.tick", "train.step")  # what a loop thread's line holds
TRACE_ROOT = os.path.join(cluster.WORK_DIR, "trace")


@dataclass(frozen=True)
class Span:
    name: str
    start_ns: int
    duration_ns: int
    args: dict

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.duration_ns


@dataclass
class HostSpans:
    """The program's spans by thread line, each line sorted by start, and
    what places chip 0's clock on the host's."""

    lines: List[List[Span]]
    # nanoseconds to add to a time of chip 0's plane to read it on the host
    # plane's clock (module docstring); None where no program of the
    # capture has both its enqueue and its execution on record
    device_clock_offset_ns: Optional[int] = None

    def named(self, name: str) -> List[Span]:
        """Every span called ``name``, of any thread, by start."""
        return sorted((s for line in self.lines for s in line
                       if s.name == name), key=lambda s: s.start_ns)

    def loop_line(self) -> List[Span]:
        """The line of the program's loop thread: the one that holds most
        ``engine.tick`` or ``train.step`` spans ([] where none does)."""
        count = lambda line: sum(s.name in LOOP_SPAN for s in line)  # noqa: E731
        best = max(self.lines, key=count, default=[])
        return best if count(best) else []


@functools.lru_cache(maxsize=2)
def _parse(path: str, mtime_ns: int) -> HostSpans:
    from jax.profiler import ProfileData

    lines: List[List[Span]] = []
    enqueued: Dict[int, int] = {}  # run_id -> start of its enqueue, host
    started: Dict[int, int] = {}   # run_id -> start of its execution, chip 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name == CHIP0_PLANE:
            for line in plane.lines:
                if line.name == T.MODULES_LINE:
                    for ev in line.events:
                        run_id = dict(ev.stats).get("run_id")
                        if run_id is not None:
                            started[run_id] = int(ev.start_ns)
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                if ev.name.startswith(PROGRAM_SPAN):
                    spans.append(Span(ev.name, int(ev.start_ns),
                                      int(ev.duration_ns), dict(ev.stats)))
                elif ev.name == ENQUEUE:
                    stats = dict(ev.stats)
                    # every chip counts its own runs: chip 0's only
                    if stats.get("device_ordinal") == 0 and "run_id" in stats:
                        enqueued[stats["run_id"]] = int(ev.start_ns)
            if spans:
                # a parent before the child that starts with it
                lines.append(sorted(
                    spans, key=lambda s: (s.start_ns, -s.duration_ns)))
    return HostSpans(lines, _clock_offset_ns(
        [enqueued[r] - started[r] for r in enqueued if r in started]))


def _clock_offset_ns(behind: Sequence[int]) -> Optional[int]:
    """From how long before its own enqueue each program's execution reads:
    the most of them, a program that reads over a millisecond more than
    the median left out (launch latencies differ by tenths of that; such a
    pair is two runs that share a number, not a launch)."""
    if not behind:
        return None
    median = statistics.median(behind)
    return max(b for b in behind if b - median <= 1_000_000)


def load(path: Optional[str] = None) -> Optional[HostSpans]:
    """The spans of the newest trace under ``path`` (default: where the
    runners put this run's); None where there is no trace or no span.
    Parsed once a file: six readers ask in one process."""
    try:
        found = T.find_xplane(path or TRACE_ROOT)
        spans = _parse(found, os.stat(found).st_mtime_ns)
    except (OSError, ValueError):
        return None
    return spans if spans.lines else None


# ------------------------------------------------------------- arithmetic


def mean_ms(spans: Sequence[Span]) -> Optional[float]:
    if not spans:
        return None
    return statistics.fmean(s.duration_ns for s in spans) / 1e6


def mean_duration_ms(name: str) -> Optional[float]:
    """Mean duration of the spans called ``name`` in this run's trace."""
    spans = load()
    return None if spans is None else mean_ms(spans.named(name))


def leaves(line: Sequence[Span]) -> List[Span]:
    """The spans of one thread's line that hold no other span."""
    out: List[Span] = []
    for i, s in enumerate(line):  # sorted by start: a child follows at once
        nxt = line[i + 1] if i + 1 < len(line) else None
        if nxt is None or nxt.start_ns >= s.end_ns:
            out.append(s)
    return out


def children(line: Sequence[Span], parent: Span) -> List[Span]:
    """Spans of ``line`` that lie inside ``parent``, by start."""
    return [s for s in line if s is not parent
            and s.start_ns >= parent.start_ns and s.end_ns <= parent.end_ns]


def idle_intervals(dev: T.DeviceTrace, offset_ns: int
                   ) -> List[Tuple[int, int]]:
    """(start, end), on the host's clock, of every stretch of the chip's op
    line with nothing running, between its first and its last operation."""
    gaps: List[Tuple[int, int]] = []
    if not dev.ops:
        return gaps
    end = dev.ops[0][1] + dev.ops[0][2]
    for _, start, dur in dev.ops[1:]:
        if start > end:
            gaps.append((end + offset_ns, start + offset_ns))
        end = max(end, start + dur)
    return gaps


def overlap_ns(gaps: Sequence[Tuple[int, int]],
               spans: Sequence[Span]) -> int:
    """Length of ``gaps`` covered by the union of ``spans`` (both sorted
    by start, the gaps disjoint)."""
    cover: List[Tuple[int, int]] = []
    for s in spans:  # union of the spans
        if cover and s.start_ns <= cover[-1][1]:
            cover[-1] = (cover[-1][0], max(cover[-1][1], s.end_ns))
        else:
            cover.append((s.start_ns, s.end_ns))
    total, j = 0, 0
    for lo, hi in gaps:
        while j < len(cover) and cover[j][1] <= lo:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < hi:
            total += min(hi, cover[k][1]) - max(lo, cover[k][0])
            k += 1
    return total


def idle_unattributed_share(trace: Optional[T.Trace]) -> Optional[float]:
    """Of chip 0's idle time while the program's loop thread was on record,
    the percentage that lies under no leaf span of that thread.
    ``engine.idle`` counts as a name: waiting for a request is an answer.
    What is left is idleness the spans cannot yet explain. A span that was
    open when the capture began is not recorded, so the idle time before
    the thread's first recorded span (and after its last) is left out: it
    cannot be judged."""
    spans = load()
    if (spans is None or spans.device_clock_offset_ns is None
            or trace is None or not trace.devices):
        return None
    line = spans.loop_line()
    if not line:
        return None
    lo, hi = line[0].start_ns, max(s.end_ns for s in line)
    gaps = [(max(a, lo), min(b, hi)) for a, b in idle_intervals(
        trace.devices[0], spans.device_clock_offset_ns) if a < hi and b > lo]
    idle = sum(b - a for a, b in gaps)
    if idle <= 0:
        return None
    return 100.0 * (idle - overlap_ns(gaps, leaves(line))) / idle


def ticks_with_program(line: Sequence[Span], dev: T.DeviceTrace,
                       program: str, offset_ns: int
                       ) -> List[Tuple[Span, Tuple[int, int]]]:
    """(``engine.tick`` span, (start on the host's clock, duration) of the
    execution of ``program`` on ``dev`` that began inside it), for every
    tick the capture holds whole with its program."""
    runs = sorted((start + offset_ns, dur)
                  for start, dur in T.programs(dev).get(program, []))
    out, j = [], 0
    for tick in (s for s in line if s.name == "engine.tick"):
        while j < len(runs) and runs[j][0] < tick.start_ns:
            j += 1
        if j < len(runs) and runs[j][0] < tick.end_ns:
            out.append((tick, runs[j]))
    return out


def gap_fill(dev: T.DeviceTrace, line: Sequence[Span], offset_ns: int,
             n: int = 10) -> List[dict]:
    """The chip's ``n`` longest idle gaps, each with the milliseconds of it
    that lie under every leaf span name of the loop thread."""
    leaf = leaves(line)
    out = []
    for lo, hi in sorted(idle_intervals(dev, offset_ns),
                         key=lambda g: g[0] - g[1])[:n]:
        under: Dict[str, float] = {}
        for s in leaf:
            if s.start_ns < hi and s.end_ns > lo:
                under[s.name] = under.get(s.name, 0.0) + (
                    min(hi, s.end_ns) - max(lo, s.start_ns)) / 1e6
        out.append({"gap_ms": (hi - lo) / 1e6, "under": {
            k: round(v, 3) for k, v in sorted(
                under.items(), key=lambda kv: -kv[1])}})
    return out


def summary(path: str) -> dict:
    spans, trace = load(path), T.load(path)
    if spans is None:
        return {"lines": 0}
    by_name: Dict[str, List[Span]] = {}
    for line in spans.lines:
        for s in line:
            by_name.setdefault(s.name, []).append(s)
    loop = spans.loop_line()
    dev = trace.devices[0] if trace.devices else None
    out = {
        "lines": len(spans.lines), "loop_line_spans": len(loop),
        "spans": {name: {"count": len(ss), "mean_ms": mean_ms(ss),
                         "last_args": ss[-1].args}
                  for name, ss in sorted(by_name.items())},
    }
    offset = spans.device_clock_offset_ns
    out["device_clock_offset_ms"] = None if offset is None else offset / 1e6
    if dev is not None and loop and offset is not None:
        gaps = idle_intervals(dev, offset)
        out["chip0_idle_ms"] = sum(hi - lo for lo, hi in gaps) / 1e6
        out["chip0_idle_under_leaf_spans_ms"] = overlap_ns(
            gaps, leaves(loop)) / 1e6
        out["longest_gaps"] = gap_fill(dev, loop, offset)
    return out


if __name__ == "__main__":
    import json

    print(json.dumps(summary(sys.argv[1]), indent=1))
