"""Where a traced train step's routed layers spend their time, from a
profile directory, in one command:

    python3 scripts/trace_ops_by_scope.py chiprun_out/trace_p41 [--program jit_step_fn] [--top 6]

Own milliseconds a step (chip 0, whole executions of the program only) by
``moe.*`` scope x pass (forward / remat: what a checkpoint runs again in the
backward pass / backward: a transposed operation) x HLO operation (with
the JAX primitive its ``op_name`` ends in: a fusion carries its root's), the
grouped-product calls (``ops/grouped_matmul.py``'s ``grouped_matmul`` and
``grouped_matmul_dw`` by pass and result shape; ``ragged-dot-none``, the
compiler's, in a trace from before PR 44, by result shape) and every
operation of a scatter primitive by result shape (a count made by a
scatter-add of ones shows as an ``s32[experts]`` result). The readers are
the benchmark's own (``benchmarks/lib/op_scopes.py``,
``benchmarks/lib/moe_ops.py``) but for one thing: JAX writes a forward
operation that lies directly under a scope as ``jvp(moe.route)/top_k``, the
scope INSIDE the transform's brackets, which ``op_scopes.scope_of`` (a scope
is a whole path element) does not find; this file finds it, and prints
beside its own sum the one the benchmark's readers make. No cell runs this
file. ISSUE 41's table came from PR 40's trace by hand; this prints it from
any.

Since PR 56 also the attention's rows of a training step, named here (the
benchmark's list is ``benchmarks/lib/moe_ops.py:SCOPES``): the ``mla.*`` and
``mtp.*`` scopes (an operation counts under its innermost one), the flash
kernels by their instruction's name, ``attn.fold`` (every pad, slice, repeat
or transpose that still stands between a projection and a flash call:
``ops/attention.py``, its ``swapped`` among them), and, so that a trace
from before that scope reads beside one with it, the copies and transposes
of whole arrays that lie under no scope at all.
"""
from __future__ import annotations

import argparse
import collections
import os
import re
import statistics
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import moe_ops, op_scopes  # noqa: E402
from benchmarks.lib import trace as T  # noqa: E402

RESULT = re.compile(r" = \(?(\w+\[[\d,]*\])")
FLASH, UNSCOPED_COPIES = "flash kernels", "copies under no scope"
SCOPES = moe_ops.SCOPES + (
    "mla.q", "mla.down", "mla.up", "mla.out", "mtp.in", "mtp.block",
    "mtp.head", "attn.fold")
SCOPE = re.compile(r"(?:^|[/(])(%s)(?=[/):]|$)" % "|".join(
    re.escape(s) for s in SCOPES))


def scope_of(meta: op_scopes.OpMeta):
    """(the operation's row: a flash kernel by its name, else its innermost
    scope of ``SCOPES``, else a copy's own row or None; whether the
    benchmark's ``moe.*`` readers find it)."""
    name = T.instruction_name(meta.text)
    if T.is_kernel(meta.text) and "flash_" in name:
        return FLASH, False
    theirs = moe_ops.scope_of(meta)
    if theirs is not None:
        return theirs, True
    found = SCOPE.findall(meta.op_name)
    if found:
        return found[-1], False
    moved = "copy" in name or "transpose" in name
    return (UNSCOPED_COPIES if moved else None), False


# the grouped products' kernels by instruction name: this repo's (PR 44 on)
# and the compiler's own for a ``ragged_dot`` (before)
GROUPED_PRODUCTS = ("grouped_matmul", "ragged-dot-none")


def which_pass(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "remat"
    return "backward" if "transpose(" in op_name else "forward"


def operation(meta: op_scopes.OpMeta) -> str:
    """An instruction's HLO operation, first result and JAX primitive:
    ``fusion f32[16384,2560] scatter-add``, ``ragged-dot-none
    bf16[36864,768]``."""
    name = re.sub(r"[.\d]+$", "", T.instruction_name(meta.text)).lstrip("%")
    m = RESULT.search(meta.text)
    primitive = meta.op_name.rstrip(":").rsplit("/", 1)[-1]
    return " ".join(filter(None, (
        name, m and m.group(1), primitive if primitive != name else "")))


def rows(ops: op_scopes.ScopedOps, program: str):
    """(scope or None, whether the readers see it, pass, operation, own ns)
    of every execution of an operation inside the program's whole executions (the capture cuts its
    first and last: those within 2% of the median's length), their count
    and summed length."""
    steps = [(s, s + d) for name, s, d in ops.modules if program in name]
    if steps:
        median = statistics.median(e - s for s, e in steps)
        steps = [(s, e) for s, e in steps
                 if abs(e - s - median) < 0.02 * median]
    out, i = [], 0
    for mid, start, own in ops.self_ns:
        while i < len(steps) and steps[i][1] <= start:
            i += 1
        if i == len(steps):
            break
        if start < steps[i][0]:
            continue
        meta = ops.meta[mid]
        grouped = meta.op_name.rstrip(":") in moe_ops.COMPILER_NAMED
        # the compiler drops a grouped product's op_name: its pass is not
        # on record, its result's shape is
        out.append((*scope_of(meta),
                    "any" if grouped else which_pass(meta.op_name),
                    operation(meta), own))
    return out, len(steps), sum(e - s for s, e in steps)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("profile_dir")
    parser.add_argument("--program", default="jit_step_fn")
    parser.add_argument("--top", type=int, default=6,
                        help="operations shown a scope and pass")
    args = parser.parse_args(argv)
    ops = op_scopes.load(args.profile_dir)
    if ops is None:
        print(f"no TPU plane under {args.profile_dir}", file=sys.stderr)
        return 1
    found, steps, total = rows(ops, args.program)
    if not steps:
        print(f"no whole execution of {args.program}", file=sys.stderr)
        return 1
    ms = 1e-6 / steps
    print(f"{steps} executions of {args.program}, {total * ms:.2f} ms each")
    by = collections.defaultdict(lambda: collections.defaultdict(
        lambda: [0, 0]))
    read = sum(own for scope, seen, _, _, own in found
               if scope is not None and seen)
    for scope, _, direction, what, own in found:
        if scope is not None:
            cell = by[scope, direction][what]
            cell[0] += own
            cell[1] += 1
    layer = 0
    for scope in SCOPES + (FLASH, UNSCOPED_COPIES):
        under = sum(own for (s, _), c in by.items() if s == scope
                    for own, _ in c.values())
        if scope in moe_ops.SCOPES:
            layer += under
        print(f"{scope}: {under * ms:.2f} ms a step")
        for (s, direction), cells in sorted(by.items()):
            if s != scope:
                continue
            part = sum(own for own, _ in cells.values())
            print(f"  {direction}: {part * ms:.2f}")
            top = sorted(cells.items(), key=lambda kv: -kv[1][0])
            for what, (own, calls) in top[:args.top]:
                print(f"    {own * ms:8.3f}  x{calls / steps:g}  {what}")
    print(f"all moe.*: {layer * ms:.2f} ms a step, "
          f"{100.0 * layer / total:.2f}% of it (the benchmark's readers "
          f"see {read * ms:.2f} ms, {100.0 * read / total:.2f}%)")
    for title, keep in (("grouped products", GROUPED_PRODUCTS),
                        ("scatters", ("scatter",))):
        print(f"{title} (calls a step, ms a call, ms a step, result):")
        seen = collections.defaultdict(lambda: [0, 0])
        for _, _, direction, what, own in found:
            word = what.split(" ")[0 if keep is GROUPED_PRODUCTS else -1]
            if any(k in word for k in keep):
                if keep is GROUPED_PRODUCTS and direction != "any":
                    what = f"{what} [{direction}]"
                seen[what][0] += own
                seen[what][1] += 1
        for what, (own, calls) in sorted(seen.items(),
                                         key=lambda kv: -kv[1][0]):
            print(f"  x{calls / steps:<5g} {own / calls * 1e-6:7.3f} "
                  f"{own * ms:8.3f}  {what}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
