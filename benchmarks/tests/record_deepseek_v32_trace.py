"""Cuts a capture of the DeepSeek-V3.2-Exp cell down to the small trace under
``benchmarks/tests/data`` (``python3
benchmarks/tests/record_deepseek_v32_trace.py <trace dir or .xplane.pb>
<out.pb>``): chip 0's plane with its op and module lines, and the host plane,
both cut to the span of the capture's shortest whole admission of two chunks
or more with the decode ticks behind it (20 ms before, 60 ms after); the
other planes, lines and unused metadata are left out. Bytes are copied, not
re-made (``record_moe_trace.py``'s functions): what stays is what the
profiler wrote. The capture was PR 57's traced run of
``deepseek-v3.2-exp.serve-longdoc`` on one v5e. Kept so the recorded file
has a provenance; no test runs it."""
import sys

from benchmarks.lib import host_spans
from benchmarks.lib import op_scopes as wire
from benchmarks.lib import trace as T
from benchmarks.tests.record_moe_trace import KEEP_LINES, _cut_line, _field


def main(src: str, dst: str) -> None:
    spans = host_spans.load(src)
    admits = [a for a in spans.named("engine.admit")
              if a.args.get("index_positions")
              and a.args.get("chunks", 0) >= 2]
    first = min(admits, key=lambda a: a.args["chunks"])
    lo, hi = first.start_ns - 20_000_000, first.end_ns + 60_000_000
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
                if name == "/host:CPU" or line_name in KEEP_LINES:
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
    print(dst, len(out), "bytes", "admission of", first.args.get("chunks"),
          "chunks,", first.args.get("index_positions"), "positions scored")


if __name__ == "__main__":
    main(*sys.argv[1:])
