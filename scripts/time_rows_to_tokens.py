"""``ops/rows_to_tokens.py``'s kernel alone on the chip beside XLA's
scatter-add, at the shapes its rule (``engages``) was set by and at the rule's
edges (PR 52). Run it through the chip tool, from the root of the repo:

    python3 scripts/time_rows_to_tokens.py [shape ...]    (default: all)

A shape is a routing: T tokens choose k of X experts, of which this chip
holds the first ``held``; the pairs of held experts, stably sorted by expert,
are the buffer's rows (``parallel/moe.py:_grouped_share``), R of them by the
share's bound with NaN past the last pair. ``uneven`` sends every other held
expert three times the pairs of its neighbour (a run passes the chunk a visit
copies, so the kernel takes second rounds); ``one-group`` sends every token's
first pair to one expert and no other pair to the share. An ``edge.*`` shape
is one of ``tests/test_tpu_compile.py``'s: the most rows, visits, VMEM and
groups the rule admits.

One jitted function a variant, two warm calls, then six inside one
``jax.profiler.trace``: the ``XLA Modules`` line's median, ``bounds``
included. Each result is held to the float32 sum rounded once. One JSON line
a variant on stdout and appended to ``chiprun_out/time_rows_to_tokens.jsonl``.
A model of another width than 2,560 should read both sides again before it
trusts ``ROWS_A_VISIT``: XLA's cost a row triples once its result leaves VMEM.
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

from benchmarks.lib import trace as tr
from ray_tpu.ops import rows_to_tokens as rt

F32, BF16 = jnp.float32, jnp.bfloat16
# name -> (T, k, X, held, D, routing, [(dtype, buffers)])
SHAPES = {
    "cell": (16384, 6, 64, 16, 2560, "even",
             [(F32, 1), (BF16, 1), (BF16, 2)]),
    "cell.uneven": (16384, 6, 64, 16, 2560, "uneven", [(F32, 1), (BF16, 2)]),
    "cell.one-group": (16384, 6, 64, 16, 2560, "one-group",
                       [(F32, 1), (BF16, 2)]),
    "ling.chunk": (2048, 8, 512, 128, 2560, "even", [(F32, 1)]),
    "ling.bucket": (512, 8, 512, 128, 2560, "even", [(F32, 1)]),
    "ling.tick": (64, 8, 512, 128, 2560, "even", [(F32, 1)]),
    # R = 65,536 by the share's bound: T x k x held / X x 1.5
    "edge.most-rows": (32768, 8, 96, 16, 2560, "even", [(F32, 1)]),
    "edge.most-visits": (32768, 8, 768, 128, 2560, "even", [(F32, 1)]),
    "edge.most-vmem": (12288, 16, 72, 16, 2560, "even",
                       [(F32, 1), (BF16, 2)]),
    "edge.most-groups": (512, 8, 768, 200, 2560, "even", [(F32, 1)]),
}


def routed(T, k, X, held, D, routing, dtype, seed=0):
    """(rows [R, D] with NaN past the last pair, token [R], sizes [held],
    pairs held, R) of one routing."""
    rng = np.random.default_rng(seed)
    R = min(T * k, 8 * -(-int(T * k * held / X * 1.5) // 8))
    if routing == "one-group":      # a token names an expert once: T rows
        expert = np.full(T * k, held)
        expert[::k] = 0
    else:
        weight = np.ones(X)
        if routing == "uneven":     # the share's pairs in all as they were
            weight[:held:2], weight[1:held:2] = 1.5, 0.5
        noise = rng.random((T, X)) ** (1 / weight)   # k distinct, weighted
        expert = np.argsort(-noise, axis=1)[:, :k].reshape(-1)
        expert = np.where(expert < held, expert, held)
    order = np.argsort(expert, kind="stable")
    sizes = np.bincount(expert, minlength=held + 1)[:held]
    total = int(sizes.sum())
    assert total <= R, (total, R)
    rows = rng.standard_normal((R, D)).astype(np.float32)
    rows[total:] = np.nan
    return (jnp.asarray(rows, dtype), jnp.asarray(order[:R] // k, jnp.int32),
            jnp.asarray(sizes, jnp.int32), total, R)


def timed(fn, args, name):
    """(result, median ms of six executions) of ``jit(fn)`` under ``name``."""
    fn.__name__ = name
    f = jax.jit(fn)
    for _ in range(2):
        out = f(*args).block_until_ready()
    where = tempfile.mkdtemp(prefix="time_rows_to_tokens.")
    with jax.profiler.trace(where):
        for _ in range(6):
            out = f(*args)
        out.block_until_ready()
    dev = tr.load(where).devices[0]
    shutil.rmtree(where, ignore_errors=True)
    runs = [ns for n, events in tr.programs(dev).items() if name in n
            for _, ns in events]
    return out, statistics.median(runs) / 1e6


def main(names):
    os.makedirs("chiprun_out", exist_ok=True)
    log = open(os.path.join("chiprun_out", "time_rows_to_tokens.jsonl"), "a")

    def say(**row):
        print(json.dumps(row), flush=True)
        log.write(json.dumps(row) + "\n")
        log.flush()

    for shape in names:
        T, k, X, held, D, routing, kinds = SHAPES[shape]
        for dtype, n in kinds:
            rows, token, sizes, total, R = routed(
                T, k, X, held, D, routing, dtype)
            bufs = (rows,) + tuple(
                jnp.roll(rows, b, axis=1) for b in range(1, n))
            index = jnp.where(jnp.arange(R) < total, token, T)
            real = (jnp.arange(R) < total)[:, None]
            want = jnp.zeros((T, D), F32).at[index].add(
                sum(jnp.where(real, b.astype(F32), 0) for b in bufs),
                mode="drop").astype(dtype)
            tag = f"{shape}_{jnp.dtype(dtype).name}_{n}".replace(
                ".", "_").replace("-", "_")
            Tt, C = rt.tiles(R, held, T)
            tile, ends = np.asarray(token) // Tt, np.cumsum(np.asarray(sizes))
            longest = int(max(      # rows of one group in one tile
                np.bincount(tile[lo:hi]).max()
                for lo, hi in zip(np.r_[0, ends], ends) if lo < hi))
            facts = dict(
                shape=shape, dtype=jnp.dtype(dtype).name, buffers=n, R=R,
                G=held, T=T, held_pairs=total, tiling=[Tt, C],
                longest_run=longest,
                engages=rt.engages(R, held, T, D, dtype, n))

            def xla(index, *bufs):      # the adds in the rows' dtype
                return jnp.zeros((T, D), dtype).at[index].add(
                    sum(bufs[1:], bufs[0]), mode="drop")

            def kernel(token, sizes, *bufs):
                return rt._kernel.__wrapped__(
                    bufs, token, sizes, T, jnp.dtype(dtype))

            _, ms = timed(xla, (index,) + bufs, f"xla_{tag}")
            say(impl="xla", ms=ms, **facts)
            got, ms = timed(kernel, (token, sizes) + bufs, f"kernel_{tag}")
            err = float(jnp.abs(got.astype(F32) - want.astype(F32)).max())
            say(impl="kernel", ms=ms, max_err=err, **facts)


if __name__ == "__main__":
    main(sys.argv[1:] or list(SHAPES))
