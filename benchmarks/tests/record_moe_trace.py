"""Cuts a capture of the OLMoE engine down to the small trace under
``benchmarks/tests/data`` (``python3 benchmarks/tests/record_moe_trace.py
<trace dir or .xplane.pb> <out.pb> [decode runs]``): chip 0's plane with its
op and module lines, and the host plane, both cut to the span of the first
few decode programs; the other planes, lines and unused metadata are left
out. Bytes are copied, not re-made: what stays is what the profiler wrote.
The capture was PR 27's second chip call (one v5e, ``DecodeEngine`` at the
published widths, three requests). Kept so the recorded file has a
provenance; no test runs it."""
import sys

from benchmarks.lib import op_scopes as wire
from benchmarks.lib import trace as T

KEEP_LINES = (T.OPS_LINE, T.MODULES_LINE)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _cut_line(line: bytes, lo_ns: int, hi_ns: int, used: set) -> bytes:
    fields = list(wire._fields(line))
    t0 = next((wire._signed(v) for n, v in fields if n == 3), 0)
    out = b""
    for n, v in fields:
        if n != 4:
            out += _field(n, v)
            continue
        ev = dict(wire._fields(v))
        start = t0 + wire._signed(ev.get(2, 0)) // 1000
        if lo_ns <= start < hi_ns:
            used.add(ev.get(1, 0))
            out += _field(n, v)
    return out


def main(src: str, dst: str, runs: int = 4) -> None:
    ops = wire.load(src)
    decode = [(s, d) for name, s, d in ops.modules if "jit_decode" in name]
    lo, hi = decode[0][0] - 200_000_000, decode[runs - 1][0] + decode[
        runs - 1][1] + 500_000
    with open(T.find_xplane(src), "rb") as f:
        space = f.read()
    out = b""
    for n, plane in wire._fields(space):
        if n != 1:
            continue
        fields = list(wire._fields(plane))
        name = next(v for k, v in fields if k == 2).decode()
        if name not in ("/device:TPU:0", "/host:CPU"):
            continue
        used: set = set()
        lines = b""
        for k, v in fields:
            if k == 3:
                line_name = next(
                    (x for j, x in wire._fields(v) if j == 2), b"").decode()
                if name == "/host:CPU":
                    # the host's clock reads ahead of the chip's by a
                    # millisecond or two: a wider cut on that side
                    lines += _field(3, _cut_line(v, lo, hi + 5_000_000, used))
                elif line_name in KEEP_LINES:
                    lines += _field(3, _cut_line(v, lo, hi, used))
        body = b""
        for k, v in fields:
            if k == 3:
                continue
            if k == 4 and dict(wire._fields(v)).get(1) not in used:
                continue
            body += _field(k, v)
        out += _field(1, body + lines)
    with open(dst, "wb") as f:
        f.write(out)
    print(dst, len(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(int(a) for a in sys.argv[3:4]))
