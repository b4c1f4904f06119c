"""A lightning indexer's two steps alone on the chip, XLA's forms (what the
program ran before PR 58, and the kernels' oracle) beside the two kernels of
``ray_tpu/ops/index_select.py``, at the sizes of
``deepseek-v3.2-exp.serve-longdoc``. Run it through the chip tool, from the
root of the repo:

    python3 scripts/time_index_select.py [tick] [chunk] [key=value ...]

``tick``: 8 slots of 33,280 positions, ONE live at 12,000: XLA scores every
slot's whole leaf (``index_select.scores``) and chooses by eight passes of
fifteen compares (``chosen``); the kernels read the live slot's filled
blocks and count inside VMEM. ``chunk``: 2,048 queries at positions 10,240
.. 12,287 over the width of 16,384: XLA's blocks of 512 positions with the
[2048, 64, 512] products through HBM (PR 57's ``scores_of_block``, kept
below) and ``chosen``; the kernels. ``key=value`` sets a module constant of
``index_select`` for the cases after it (``chunk QUERIES=64 chunk``: a sweep
in one process). Both sides get the same inputs; the
kernels' scores are held to XLA's where a query sees them, and the choice
from ``chosen_up_to`` (``kth_largest``'s threshold) to ``chosen``'s own, bit for bit, on the SAME scores.
One jitted function a variant, two warm calls, then six inside one
``jax.profiler.trace``: the ``XLA Modules`` line's median. One JSON line a
case on stdout and appended to ``chiprun_out/time_index_select.jsonl``.
``--rehearse``: tiny shapes through the interpreter on the CPU.
"""
import json
import os
import shutil
import statistics
import sys
import tempfile

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import index_select
from ray_tpu.ops.decode_attention import live_slots


def timed(fn, args, name):
    """(result, median ms of six executions) of ``jit(fn)`` under ``name``."""
    from benchmarks.lib import trace as tr

    fn.__name__ = name
    f = jax.jit(fn)
    for _ in range(2):
        out = jax.block_until_ready(f(*args))
    if jax.default_backend() != "tpu":
        return out, None
    where = tempfile.mkdtemp(prefix="time_index_select.")
    with jax.profiler.trace(where):
        for _ in range(6):
            out = f(*args)
        jax.block_until_ready(out)
    dev = tr.load(where).devices[0]
    shutil.rmtree(where, ignore_errors=True)
    runs = [ns for n, events in tr.programs(dev).items() if name in n
            for _, ns in events]
    return out, statistics.median(runs) / 1e6


def blocks_through_hbm(q, weights, leaf, layer, filled):
    """PR 57's ``scores_of_block``: the filled blocks of ``n`` positions one
    after another, each block's [T, Hi, n] products summed over the heads
    by XLA."""
    T = q.shape[0]
    S, Di = leaf.shape[-2:]
    n = min(512, S)

    def block(i, out):
        keys = jax.lax.dynamic_slice(
            leaf, (layer, 0, i * n, 0), (1, 1, n, Di))[0, 0]
        s = jnp.einsum("thd,sd->ths", q, keys.astype(q.dtype),
                       preferred_element_type=jnp.float32)
        return jax.lax.dynamic_update_slice(
            out, index_select._weighted(weights, s), (0, i * n))

    return jax.lax.fori_loop(
        0, jnp.minimum((filled - 1) // n + 1, S // n), block,
        jnp.full((T, S), -jnp.inf, jnp.float32))


def _inputs(rng, B, T, S, Hi, Di, dtype):
    leaf = jnp.asarray(rng.normal(size=(2, B, S, Di)), dtype)
    q = jnp.asarray(rng.normal(size=(B, T, Hi, Di)), dtype)
    w = jnp.asarray(np.abs(rng.normal(size=(B, T, Hi))) + 0.1, jnp.float32)
    return leaf, q, w


def _held(got, want, visible, what):
    """The kernels' scores against XLA's where a query sees them (the sum
    over the heads runs in another order), and the row counts of a choice."""
    got, want = np.asarray(got), np.asarray(want)
    gap = np.abs(np.where(visible, got - want, 0)).max()
    assert gap <= 1e-3 * np.abs(np.where(visible, want, 0)).max(), (what, gap)
    return float(gap)


def _constants():
    return {k: getattr(index_select, k) for k in (
        "QUERIES", "POSITIONS", "STEP_POSITIONS", "ROWS", "COUNTED")}


def tick(B=8, S=33280, Hi=64, Di=128, kept=2048, filled=12000, live=(3,),
         rehearse=False):
    rng = np.random.default_rng(0)
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    leaf, q, w = _inputs(rng, B, 1, S, Hi, Di, dtype)
    alive = np.zeros(B, bool)
    alive[list(live)] = True
    lens = jnp.asarray(np.where(alive, filled, 0), jnp.int32)
    visible = np.arange(S)[None] < np.asarray(lens)[:, None]
    seen = jnp.asarray(visible)

    def xla(leaf, q, w):
        found = index_select.scores(q, w, leaf[1])[:, 0]
        return found, index_select.chosen(found, seen, kept)

    def kernels(leaf, q, w):
        found = index_select.scores_of_step(
            q[:, 0], w[:, 0], leaf, 1, lens, live_slots(jnp.asarray(alive)),
            interpret=rehearse)
        return found, index_select.chosen_up_to(
            found, lens - 1, kept, interpret=rehearse)

    def choice_alone(found):
        return index_select.chosen(found, seen, kept)

    (want, _), xla_ms = timed(xla, (leaf, q, w), "tick_xla")
    (got, picked), kernel_ms = timed(kernels, (leaf, q, w), "tick_kernels")
    rows = np.flatnonzero(alive)
    gap = _held(got[rows], want[rows], visible[rows], "tick")
    same, _ = timed(choice_alone, (got,), "tick_choice")
    assert np.array_equal(np.asarray(picked)[rows], np.asarray(same)[rows])
    return {"case": "tick", "slots": B, "live": len(rows), "filled": filled,
            "xla_ms": xla_ms, "kernels_ms": kernel_ms, "largest_gap": gap,
            **_constants()}


def chunk(T=2048, S=33280, Hi=64, Di=128, kept=2048, start=10240,
          width=16384, rehearse=False):
    rng = np.random.default_rng(1)
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    leaf, q, w = _inputs(rng, 1, T, S, Hi, Di, dtype)
    pos = start + jnp.arange(T)
    seen = jnp.arange(width)[None, :] <= pos[:, None]

    def xla_scores(leaf, q, w):
        return blocks_through_hbm(q[0], w[0], leaf, 1, start + T)[:, :width]

    def xla_choice(found):
        return index_select.chosen(found, seen, kept)

    def kernel_scores(leaf, q, w):
        return index_select.scores_of_block(
            q[0], w[0], leaf, 1, start, width=width, interpret=rehearse)

    def kernel_choice(found):
        return index_select.chosen_up_to(found, pos, kept, interpret=rehearse)

    want, xla_scores_ms = timed(xla_scores, (leaf, q, w), "chunk_xla_scores")
    got, scores_ms = timed(kernel_scores, (leaf, q, w), "chunk_kernel_scores")
    gap = _held(got, want, np.asarray(seen), "chunk")
    same, xla_choice_ms = timed(xla_choice, (got,), "chunk_xla_choice")
    picked, choice_ms = timed(kernel_choice, (got,), "chunk_kernel_choice")
    assert np.array_equal(np.asarray(picked), np.asarray(same))
    return {"case": "chunk", "tokens": T, "start": start, "width": width,
            "xla_scores_ms": xla_scores_ms, "kernel_scores_ms": scores_ms,
            "xla_choice_ms": xla_choice_ms, "kernel_choice_ms": choice_ms,
            "largest_gap": gap,
            **_constants()}


def main(args):
    if "--rehearse" in args:
        index_select.POSITIONS = index_select.COUNTED = 32
        print(json.dumps(tick(B=3, S=256, Hi=4, Di=16, kept=16, filled=90,
                              live=(1,), rehearse=True)))
        print(json.dumps(chunk(T=32, S=256, Hi=4, Di=16, kept=16, start=48,
                               width=128, rehearse=True)))
        return
    os.makedirs("chiprun_out", exist_ok=True)
    for a in args or ["tick", "chunk"]:     # in order: a constant, then a case
        if "=" in a:
            setattr(index_select, a.split("=")[0], int(a.split("=")[1]))
            continue
        row = {"tick": tick, "chunk": chunk}[a]()
        print(json.dumps(row), flush=True)
        with open("chiprun_out/time_index_select.jsonl", "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
