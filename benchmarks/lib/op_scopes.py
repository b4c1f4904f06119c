"""Which ``jax.named_scope`` each device operation of a trace ran under.

``jax.profiler.ProfileData`` gives an operation's name, start and duration,
and not what the ``.xplane.pb`` holds beside them: the event's METADATA,
whose stat ``tf_op`` is the operation's ``op_name`` as JAX wrote it, scopes
included (``jit(decode)/while/body/moe.experts/ragged_dot_general``), and
whose stat ``program_id`` is the number in the program's ``XLA Modules``
event (``jit_decode(<program_id>)``). So this file reads the protobuf's wire
format itself, the few messages of ``xplane.proto`` it needs and nothing
else (field numbers below; ``benchmarks/tests/test_moe_ops.py`` holds it against a
recorded trace):

    XSpace.planes = 1
    XPlane: name = 2, lines = 3, event_metadata = 4 (map), stat_metadata = 5
    XLine: name = 2, timestamp_ns = 3, events = 4
    XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3
    XEventMetadata: id = 1, name = 2, stats = 5
    XStatMetadata: id = 1, name = 2
    XStat: metadata_id = 1, uint64 = 3, int64 = 4, str = 5, ref = 7

A fusion carries ONE ``op_name``, its root's: an operation fused across a
scope's edge is counted with the scope of the fusion's root. The op line
nests (a ``while`` spans its body), so times are an operation's own
(``self_ns``), as ``lib/trace.py:self_times`` has them.
"""
from __future__ import annotations

import functools
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple

from benchmarks.lib import host_spans
from benchmarks.lib import trace as T


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, bytes for
    a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane message")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


@dataclass(frozen=True)
class OpMeta:
    text: str          # the instruction's whole HLO text
    op_name: str       # ``tf_op``: JAX's op_name, scopes included
    program_id: int    # of the program the instruction belongs to


@dataclass
class ScopedOps:
    """Chip 0's op line with each operation's metadata."""

    ops: List[Tuple[int, int, int]]   # (metadata id, start_ns, duration_ns)
    meta: Dict[int, OpMeta]
    modules: List[Tuple[str, int, int]]  # (event name, start_ns, dur_ns)

    def program_ids(self, contains: str) -> Dict[int, str]:
        """program_id -> name of the programs whose name has ``contains``."""
        out = {}
        for name, _, _ in self.modules:
            m = re.search(r"\((\d+)\)$", name)
            if m and contains in name:
                out[int(m.group(1))] = T.program_name(name)
        return out

    @functools.cached_property
    def self_ns(self) -> List[Tuple[int, int, int]]:
        """(metadata id, start_ns, own nanoseconds) of every execution: its
        duration less that of the operations inside it. Computed once: three
        readers ask."""
        out: List[List[int]] = []
        stack: List[int] = []  # indices into out
        ends: List[int] = []
        for mid, start, dur in self.ops:  # sorted by start
            while stack and ends[-1] <= start:
                stack.pop(), ends.pop()
            if stack:
                out[stack[-1]][2] -= dur
            out.append([mid, start, dur])
            stack.append(len(out) - 1), ends.append(start + dur)
        return [(m, s, max(own, 0)) for m, s, own in out]


def _stat_value(stat: bytes, stat_names: Dict[int, str]):
    name = value = None
    for number, v in _fields(stat):
        if number == 1:
            name = stat_names.get(v)
        elif number in (3, 4):
            value = _signed(v) if number == 4 else v
        elif number == 5:
            value = v.decode("utf-8", "replace")
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(entry: bytes) -> bytes:
    return next((v for number, v in _fields(entry) if number == 2), b"")


def _chip0(plane: bytes) -> Optional[ScopedOps]:
    name, lines, metas, stat_meta = None, [], [], []
    for number, v in _fields(plane):
        if number == 2:
            name = v.decode()
        elif number == 3:
            lines.append(v)
        elif number == 4:
            metas.append(v)
        elif number == 5:
            stat_meta.append(v)
    if name != host_spans.CHIP0_PLANE:
        return None
    stat_names: Dict[int, str] = {}
    for entry in stat_meta:
        fields = dict(_fields(_map_entry(entry)))
        stat_names[fields.get(1, 0)] = fields.get(2, b"").decode()
    meta: Dict[int, OpMeta] = {}
    for entry in metas:
        mid, text, stats = 0, "", {}
        for number, v in _fields(_map_entry(entry)):
            if number == 1:
                mid = v
            elif number == 2:
                text = v.decode("utf-8", "replace")
            elif number == 5:
                key, value = _stat_value(v, stat_names)
                stats[key] = value
        meta[mid] = OpMeta(text, str(stats.get("tf_op") or ""),
                           int(stats.get("program_id") or 0))
    ops: List[Tuple[int, int, int]] = []
    modules: List[Tuple[str, int, int]] = []
    for line in lines:
        line_name, t0_ns, events = "", 0, []
        for number, v in _fields(line):
            if number == 2:
                line_name = v.decode()
            elif number == 3:
                t0_ns = _signed(v)
            elif number == 4:
                events.append(v)
        if line_name not in (T.OPS_LINE, T.MODULES_LINE):
            continue
        for ev in events:
            fields = dict(_fields(ev))
            start = t0_ns + _signed(fields.get(2, 0)) // 1000
            dur = fields.get(3, 0) // 1000
            if line_name == T.OPS_LINE:
                ops.append((fields.get(1, 0), start, dur))
            else:
                modules.append((meta[fields.get(1, 0)].text, start, dur))
    ops.sort(key=lambda e: (e[1], -e[2]))
    modules.sort(key=lambda e: e[1])
    return ScopedOps(ops, meta, modules)


@functools.lru_cache(maxsize=2)
def _parse(path: str, mtime_ns: int) -> Optional[ScopedOps]:
    with open(path, "rb") as f:
        space = f.read()
    for number, plane in _fields(space):
        if number == 1:
            found = _chip0(plane)
            if found is not None:
                return found
    return None


def load(path: Optional[str] = None) -> Optional[ScopedOps]:
    """Chip 0's operations of the newest trace under ``path`` (default:
    where the runners put this run's); None where there is no trace or it
    holds no TPU plane (a CPU trace). Parsed once a file."""
    try:
        found = T.find_xplane(path or host_spans.TRACE_ROOT)
        return _parse(found, os.stat(found).st_mtime_ns)
    except (OSError, ValueError):
        return None


def scope_of(op_name: str, scopes) -> Optional[str]:
    """The first of ``scopes`` that is a path element of ``op_name``."""
    parts = op_name.rstrip(":").split("/")
    return next((s for s in scopes if s in parts), None)
