"""What the spans say of one request and of one admission (PR 36).

Two things ``host_spans.HostSpans`` does not hand out, for the readers of the
request's ledger (``engine.finish`` [queued_ms, admit_ms, stalled_ms,
total_ms], ``engine.admit`` [queued_ms, held], ``llm.request``
[since_call_ms], ``llm.done`` [after_finish_ms]):

- ``mean(name, key)``: an argument over every span of a name. A program
  without the argument (the commits before PR 36) gives none, and a reader
  built on it returns ``None``.
- ``admit_device_ms``: the chip's time inside an admission, paired by
  identity and not by shifted time. The runtime's ``DoEnqueueProgram`` host
  event is on the spans' own clock and carries the ``run_id`` of the
  program's ``XLA Modules`` event on the chip: a program belongs to an
  admission when its enqueue starts inside the ``engine.admit`` span, and
  its device time is that event's duration. No time crosses the planes, so
  ``HostSpans.device_clock_offset_ns`` (2-3 ms off since PR 33, PERF.md
  section 7) is not needed.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.lib import host_spans
from benchmarks.lib import trace as T

# (start of the enqueue on the host's clock, the program's name on chip 0,
# its device time), all in nanoseconds; name None and time 0 where the
# capture does not hold the program's execution
Enqueued = Tuple[int, Optional[str], int]


def mean(name: str, key: str) -> Optional[float]:
    """Mean of the argument ``key`` over the spans called ``name`` in this
    run's trace that carry it (None where there is no trace, no such span
    or no such argument)."""
    spans = host_spans.load()
    if spans is None:
        return None
    got = [s.args[key] for s in spans.named(name) if key in s.args]
    return statistics.fmean(got) if got else None


def enqueued(path: Optional[str] = None) -> List[Enqueued]:
    """Chip 0's programs of the newest trace under ``path`` (default: this
    run's) by the start of their enqueue; [] where there is no trace."""
    from jax.profiler import ProfileData

    try:
        found = T.find_xplane(path or host_spans.TRACE_ROOT)
    except OSError:
        return []
    enqueues: List[Tuple[int, int]] = []  # (start, run_id), chip 0's only
    ran: Dict[int, List[Tuple[str, int]]] = {}  # run_id -> (name, duration)
    for plane in ProfileData.from_file(found).planes:
        if plane.name == host_spans.CHIP0_PLANE:
            for line in plane.lines:
                if line.name == T.MODULES_LINE:
                    for ev in line.events:
                        run_id = dict(ev.stats).get("run_id")
                        if run_id is not None:
                            ran.setdefault(run_id, []).append(
                                (T.program_name(ev.name),
                                 int(ev.duration_ns)))
        elif plane.name == host_spans.HOST_PLANE:
            # the runtime enqueues on threads of its own, never the loop's
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == host_spans.ENQUEUE:
                        stats = dict(ev.stats)
                        if (stats.get("device_ordinal") == 0
                                and "run_id" in stats):
                            enqueues.append(
                                (int(ev.start_ns), stats["run_id"]))
    # a number that two runs of the capture share names neither
    counts: Dict[int, int] = {}
    for _, run_id in enqueues:
        counts[run_id] = counts.get(run_id, 0) + 1
    out: List[Enqueued] = []
    for start, run_id in sorted(enqueues):
        runs = ran.get(run_id, [])
        whole = len(runs) == 1 and counts[run_id] == 1
        out.append((start, *(runs[0] if whole else (None, 0))))
    return out


def admit_device_ms(admits: Sequence[host_spans.Span],
                    programs: Sequence[Enqueued],
                    decode_program: str) -> Optional[float]:
    """Mean, over the ``admits`` the capture holds whole, of the device time
    of the ``programs`` enqueued inside the span: the prefill's chunks, the
    insert, and the empty slot cache's operations. An admission whose last
    program ran after the capture's end is left out; None where none is
    whole or no program was enqueued in any (a trace off the TPU).

    An admission reads the tick in flight before it begins
    (``llm/engine.py:_step_locked``), so no decode program is enqueued
    inside one; one that is means the loop's order changed and this sum is
    no longer an admission's, which is an error and not a number."""
    per_admit = []
    for admit in admits:
        inside = [p for p in programs
                  if admit.start_ns <= p[0] < admit.end_ns]
        for _, name, _ in inside:
            if name is not None and decode_program in name:
                raise RuntimeError(
                    f"{name} was enqueued inside the engine.admit of "
                    f"{admit.args.get('rid')}: an admission no longer "
                    f"reads the tick in flight first")
        if inside and all(name is not None for _, name, _ in inside):
            per_admit.append(sum(ns for _, _, ns in inside))
    return statistics.fmean(per_admit) / 1e6 if per_admit else None
