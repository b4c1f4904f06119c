"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` and nothing else. A TPU trace has one
plane per chip, ``/device:TPU:<n>``, whose line ``XLA Modules`` holds one
event per executed program (``jit_step_fn(<fingerprint>)``) and whose line
``XLA Ops`` holds one event per HLO operation, in order, on the chip's one
TensorCore. An op event's name is the instruction's whole text (``%fusion.3 =
bf16[..] fusion(..), calls=..``); it is kept under the instruction's own
name (``fusion.3``), with the text beside it for telling kernels apart. Everything below is arithmetic over those two lines:

- busy: the union of the op intervals; idle share is 1 - busy / window.
- a program's device time: the duration of its ``XLA Modules`` events.
- gap between programs: start of one minus end of the one before.
- exposed collective time: the time the op line is held by a collective
  (``all-gather``, ``all-reduce``, ``reduce-scatter``, ``all-to-all``,
  ``collective-permute``, and the ``-done`` half of an asynchronous one,
  which is the wait for the transfer). While the op line is held by one of
  these no compute runs on that core, so all of it is exposed; the part of
  an asynchronous transfer that overlaps compute never shows on the line.

``python -m benchmarks.lib.trace <trace dir or file>`` prints a summary, for
reading a trace by hand.
"""
from __future__ import annotations

import glob
import os
import re
import statistics
import sys
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-done)?(\.|$)"
)

Event = Tuple[str, int, int]  # name, start_ns, duration_ns


@dataclass
class DeviceTrace:
    """The two lines of one chip's plane, each sorted by start."""

    ordinal: int
    ops: List[Event]
    modules: List[Event]
    op_text: Dict[str, str]  # instruction name -> its whole HLO text


@dataclass
class Trace:
    devices: List[DeviceTrace]
    start_ns: int  # earliest event of any line of a chip's plane
    end_ns: int    # latest end of such an event

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def find_xplane(path: str) -> str:
    """The newest ``.xplane.pb`` under ``path`` (or ``path`` itself)."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def instruction_name(text: str) -> str:
    """``%all-reduce.5 = f32[..] all-reduce(..)`` -> ``all-reduce.5``."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    devices: List[DeviceTrace] = []
    lo, hi = None, None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None:
            # the window is what the chips' planes span: the host's plane
            # also holds the profiler's own start and stop
            continue
        lines: Dict[str, List[Event]] = {OPS_LINE: [], MODULES_LINE: []}
        op_text: Dict[str, str] = {}
        for line in plane.lines:
            for ev in line.events:
                start, dur = int(ev.start_ns), int(ev.duration_ns)
                lo = start if lo is None else min(lo, start)
                hi = start + dur if hi is None else max(hi, start + dur)
                if line.name == OPS_LINE:
                    name = instruction_name(ev.name)
                    op_text[name] = ev.name
                    lines[OPS_LINE].append((name, start, dur))
                elif line.name == MODULES_LINE:
                    lines[MODULES_LINE].append((ev.name, start, dur))
        by_start = lambda e: e[1]  # noqa: E731
        devices.append(DeviceTrace(
            int(m.group(1)), sorted(lines[OPS_LINE], key=by_start),
            sorted(lines[MODULES_LINE], key=by_start), op_text))
    devices.sort(key=lambda d: d.ordinal)
    return Trace(devices, lo or 0, hi or 0)


# ------------------------------------------------------------- arithmetic


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    """Total length covered by (start, duration) intervals."""
    total, cur_lo, cur_hi = 0, None, None
    for start, dur in sorted(intervals):
        if cur_hi is None or start > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = start, start + dur
        else:
            cur_hi = max(cur_hi, start + dur)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not trace.devices:
        return 0.0
    return statistics.fmean(
        union_ns((s, d) for _, s, d in dev.ops) for dev in trace.devices
    ) / 1e9


def program_name(event_name: str) -> str:
    """``jit_step_fn(123456)`` -> ``jit_step_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def programs(dev: DeviceTrace) -> Dict[str, List[Tuple[int, int]]]:
    """(start, duration) of every execution, by program name."""
    out: Dict[str, List[Tuple[int, int]]] = {}
    for name, start, dur in dev.modules:
        out.setdefault(program_name(name), []).append((start, dur))
    return out


def dominant_program(dev: DeviceTrace, contains: str) -> Optional[str]:
    """The program whose name contains ``contains`` and that took most
    device time (a trace may hold several jits of one function name)."""
    totals = {
        name: sum(d for _, d in runs)
        for name, runs in programs(dev).items() if contains in name
    }
    return max(totals, key=totals.get) if totals else None


def program_median_ms(dev: DeviceTrace, name: str) -> Optional[float]:
    runs = programs(dev).get(name)
    if not runs:
        return None
    return statistics.median(d for _, d in runs) / 1e6


def program_gap_mean_ms(dev: DeviceTrace, name: str) -> Optional[float]:
    """Mean time from the end of one execution of ``name`` to the start of
    the next, whatever ran in between."""
    runs = sorted(programs(dev).get(name, []))
    if len(runs) < 2:
        return None
    gaps = [
        runs[i + 1][0] - (runs[i][0] + runs[i][1])
        for i in range(len(runs) - 1)
    ]
    return statistics.fmean(gaps) / 1e6


def collective_exposed_s(dev: DeviceTrace) -> float:
    # the pattern leaves the ``-start`` half out: it only launches
    return union_ns(
        (s, d) for n, s, d in dev.ops if COLLECTIVE.match(n)) / 1e9


def self_times(ops: Sequence[Event]) -> Dict[str, int]:
    """Nanoseconds each instruction ran ITSELF, summed over its executions.
    The op line nests: a ``while`` or a call spans the ops of its body, so
    an op's own time is its duration minus that of the ops inside it."""
    totals: Dict[str, int] = {}
    stack: List[List] = []  # [name, end_ns, self_ns]

    def close(until: int) -> None:
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0) + max(own, 0)

    for name, start, dur in ops:  # sorted by start
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return totals


def is_kernel(text: str) -> bool:
    """A Pallas (Mosaic) kernel: the one custom call that is real work."""
    return 'custom_call_target="tpu_custom_call"' in text


def operand_count(text: str) -> int:
    """Operands of the instruction ``text`` describes."""
    head = text.split(" = ", 1)[-1]
    m = re.search(r"[a-z][a-z0-9-]*\(", head)
    if not m:
        return 0
    depth, commas, seen = 1, 0, False
    for ch in head[m.end():]:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                break
        elif ch == "," and depth == 1:
            commas += 1
        seen = seen or not ch.isspace()
    return commas + 1 if seen else 0


def op_label(name: str, text: str) -> str:
    """Instruction name and result shape, ``kernel:`` before a Pallas one."""
    head = re.sub(r"\{[^}]*\}", "", text.split(" = ", 1)[-1])
    m = re.match(r"(\(.*?\)|\S+)\s", head)
    shape = m.group(1) if m else ""
    label = f"{name} {shape}"[:96]
    return "kernel:" + label if is_kernel(text) else label


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The operations that took most device time themselves: [label,
    seconds] summed over executions and averaged over the chips."""
    if not trace.devices:
        return []
    totals: Dict[str, int] = {}
    text: Dict[str, str] = {}
    for dev in trace.devices:
        for name, ns in self_times(dev.ops).items():
            totals[name] = totals.get(name, 0) + ns
        text.update(dev.op_text)
    k = len(trace.devices)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[op_label(name, text.get(name, name)), ns / 1e9 / k]
            for name, ns in ranked]


def kernel_seconds(dev: DeviceTrace, min_operands: int, max_operands: int
                   ) -> Tuple[float, int, List[str]]:
    """(summed seconds, executions, the distinct instructions' texts) of the
    Pallas kernels with ``min_operands`` <= operands <= ``max_operands``."""
    hit = {n: t for n, t in dev.op_text.items()
           if is_kernel(t) and min_operands <= operand_count(t) <= max_operands}
    durs = [d for n, _, d in dev.ops if n in hit]
    return sum(durs) / 1e9, len(durs), sorted(hit.values())


def idle_gaps(dev: DeviceTrace, n: int = 10) -> List[List]:
    """The longest stretches of device 0's op line with nothing running,
    each named by the programs on either side of it (the host's own spans
    are not in the trace: the program records none yet)."""
    if not dev.ops:
        return []
    mods = dev.modules

    def label(lo: int, hi: int) -> str:
        before, after = "none", "none"
        for name, start, dur in mods:
            if start <= lo and start + dur >= hi:
                return "inside " + program_name(name)
            if start <= lo:
                before = program_name(name)
            elif after == "none":
                after = program_name(name)
        return f"after {before} before {after}"

    gaps = []
    end = dev.ops[0][1] + dev.ops[0][2]
    for _, start, dur in dev.ops[1:]:
        if start > end:
            gaps.append((start - end, end, start))
        end = max(end, start + dur)
    gaps.sort(reverse=True)
    out: List[List] = []
    for length, lo, hi in gaps[:n]:
        out.append([label(lo, hi), length / 1e9])
    return out


def breakdown(trace: Trace) -> dict:
    dev0 = trace.devices[0] if trace.devices else None
    return {
        "device_ops": top_ops(trace),
        "idle_gaps": idle_gaps(dev0) if dev0 else [],
    }


def summary(trace: Trace, top: int = 25) -> dict:
    """What a reader looks at first: planes, programs, heaviest ops."""
    out = {"window_s": trace.window_s, "busy_s": busy_s(trace),
           "devices": []}
    for dev in trace.devices:
        progs = {
            name: {"runs": len(runs),
                   "median_ms": statistics.median(d for _, d in runs) / 1e6,
                   "total_s": sum(d for _, d in runs) / 1e9}
            for name, runs in programs(dev).items()
        }
        out["devices"].append({
            "ordinal": dev.ordinal, "ops": len(dev.ops),
            "busy_s": union_ns((s, d) for _, s, d in dev.ops) / 1e9,
            "collective_exposed_s": collective_exposed_s(dev),
            "programs": progs,
        })
    out["top_ops"] = top_ops(trace, top)
    return out


if __name__ == "__main__":
    import json

    print(json.dumps(summary(load(sys.argv[1])), indent=1))
