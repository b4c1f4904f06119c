"""Cuts a capture of ``smallthinker-21b-a3b.train-seq8k``'s train step down
to the small trace under ``benchmarks/tests/data`` (``python3
benchmarks/tests/record_smallthinker_trace.py <trace dir or .xplane.pb>
<out.pb> [steps]``): chip 0's plane with its op and module lines, and the
host plane, both cut to the span of the first few whole executions of
``jit_step_fn`` (``record_moe_trace.py``'s cut, of another program). Bytes
are copied, not re-made: what stays is what the profiler wrote. The capture
was PR 40's first traced run of the cell (one v5e). Kept so the recorded file
has a provenance; no test runs it."""
import os
import sys

from benchmarks.lib import named
from benchmarks.lib import op_scopes as wire
from benchmarks.lib import trace as T

cut = named.load(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "record_moe_trace.py"))
PROGRAM = "jit_step_fn"


def main(src: str, dst: str, steps: int = 2) -> None:
    ops = wire.load(src)
    runs = [(s, d) for name, s, d in ops.modules if PROGRAM in name]
    # the first execution on record may have begun before the capture did
    runs = runs[1:steps + 1]
    lo, hi = runs[0][0] - 3_000_000, runs[-1][0] + runs[-1][1] + 500_000
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
            if k != 3:
                continue
            line_name = next(
                (x for j, x in wire._fields(v) if j == 2), b"").decode()
            if name == "/host:CPU":
                # the host's clock reads ahead of the chip's by a
                # millisecond or two: a wider cut on that side
                lines += cut._field(
                    3, cut._cut_line(v, lo, hi + 5_000_000, used))
            elif line_name in cut.KEEP_LINES:
                lines += cut._field(3, cut._cut_line(v, lo, hi, used))
        body = b"".join(
            cut._field(k, v) for k, v in fields
            if k != 3 and not (
                k == 4 and dict(wire._fields(v)).get(1) not in used))
        out += cut._field(1, body + lines)
    with open(dst, "wb") as f:
        f.write(out)
    print(dst, len(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(int(a) for a in sys.argv[3:4]))
