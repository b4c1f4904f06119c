"""The two exact forms of a decode step's attention over an indexer's choice,
alone on the chip at the lengths of ``deepseek-v3.2-exp.serve-longdoc`` (PR
57). Run it through the chip tool, from the root of the repo:

    python3 scripts/time_selected_read.py [cached positions ...]

(b) READ EVERY ROW AND MASK: ``ops/decode_attention.py:
latent_decode_attention`` with the choice beside the cache, over a
position-minor leaf ``[1, B, 1, 576, S]``: what the program runs.
(a) READ THE CHOSEN ROWS ONLY: the kernel below over a position-MAJOR leaf
``[B, S, 576]``, the chosen positions prefetched to scalar memory, a copy a
chosen position into VMEM (of the 8 positions of its tile: the chip has no
copy of one row, ``gathered_decode_attention``), then the absorbed products.
It lives here and not under ``ray_tpu/``: it lost at every length of the
cell (PERF.md section 6), and the program keeps one layout of the leaf.

B slots of 128 heads, 2,048 positions kept of ``n`` cached; both forms get
the same queries, rows and choice and their results are held to each other.
One jitted function a variant, two warm calls, then six inside one
``jax.profiler.trace``: the ``XLA Modules`` line's median. One JSON line a
length on stdout and appended to ``chiprun_out/time_selected_read.jsonl``.
``--rehearse``: tiny shapes through the interpreter on the CPU.
"""
import functools
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.decode_attention import latent_decode_attention


GROUP = 8   # positions a copy moves: a tile of the leaf's rows (below)


def _gather_kernel(idx_ref, q_ref, own_ref, rows_hbm, o_ref, buf, sem, *,
                   kept: int, scale: float, values: int):
    b = pl.program_id(0)

    def copy(i, src):
        return pltpu.make_async_copy(
            rows_hbm.at[b, pl.ds(pl.multiple_of(src, GROUP), GROUP), :],
            buf.at[pl.ds(pl.multiple_of(i * GROUP, GROUP), GROUP), :], sem)

    def issue(i, _):
        copy(i, (idx_ref[b, i] // GROUP) * GROUP).start()
        return 0

    def wait(i, _):
        copy(i, 0).wait()
        return 0

    jax.lax.fori_loop(0, kept, issue, 0)
    jax.lax.fori_loop(0, kept, wait, 0)
    rows = buf[...]                                  # [kept x GROUP, D]
    q = q_ref[0]                                     # [H, D]
    sc = jax.lax.dot_general(
        q, rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [H, kept x GROUP]
    own = own_ref[0] > 0                             # [1, kept x GROUP]
    sc = jnp.where(own, sc, -1e30)
    p = jnp.where(own, jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True)), 0.0)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    o_ref[0] = jnp.dot(p.astype(q.dtype), rows[:, :values],
                       preferred_element_type=jnp.float32).astype(o_ref.dtype)


def gathered_decode_attention(q, rows, positions, *, values: int,
                              scale: float, interpret=False):
    """q [B, H, D] over the rows ``positions`` [B, kept] of ``rows`` [B, S,
    D] (position-major) -> [B, H, values]. THE CHIP HAS NO COPY OF ONE ROW:
    a two-dimensional array lies in HBM in tiles of 8 rows x 128 lanes, a
    position's 1,152 B are nine pieces 4 KiB apart, and the compiler refuses
    a slice of fewer than 8 rows ("must be aligned to tiling (8)"; held one
    row a tile, [S, 1, 576], the leaf is eight times its size). So a copy
    moves the ``GROUP`` positions of the tile that holds a chosen one, the
    products run over all of them and the seven that were not chosen are
    masked: the least a form that reads "the chosen rows only" can do
    here."""
    B, H, D = q.shape
    kept = positions.shape[1]
    positions = positions.astype(jnp.int32)
    # and none of a tile's lanes: ``rows`` come 640 wide (five tiles)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, rows.shape[-1] - D)))
    D = rows.shape[-1]
    own = (positions[:, :, None] % GROUP == jnp.arange(GROUP)).reshape(
        B, 1, kept * GROUP).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_gather_kernel, kept=kept, scale=scale,
                          values=values),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, 1, kept * GROUP), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, values), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((kept * GROUP, D), rows.dtype),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, values), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 << 20),
        name="gathered_decode_attention",
        interpret=interpret,
    )(positions, q, own, rows)


def timed(fn, args, name, carried=None):
    """(result, median ms of six executions) of ``jit(fn)`` under ``name``.
    ``carried``: the argument that ``fn`` gives back as its second result
    (a cache a kernel writes in place): donated, and handed on from call to
    call, so that no copy of it is in the time."""
    from benchmarks.lib import trace as tr

    fn.__name__ = name
    f = jax.jit(fn, donate_argnums=() if carried is None else (carried,))
    args = list(args)

    def call():
        out = f(*args)
        if carried is None:
            return out
        args[carried] = out[1]
        return out[0]

    for _ in range(2):
        out = jax.block_until_ready(call())
    where = tempfile.mkdtemp(prefix="time_selected_read.")
    with jax.profiler.trace(where):
        for _ in range(6):
            out = call()
        jax.block_until_ready(out)
    dev = tr.load(where).devices[0]
    shutil.rmtree(where, ignore_errors=True)
    runs = [ns for n, events in tr.programs(dev).items() if name in n
            for _, ns in events]
    return out, statistics.median(runs) / 1e6


def one(n: int, B=8, H=128, R=512, Dr=64, kept=2048, S=33280, rehearse=False):
    rng = np.random.default_rng(n)
    D = R + Dr
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    q = jnp.asarray(rng.normal(size=(B, H, D)) * 0.3, dtype)
    rows = jnp.asarray(rng.normal(size=(B, S, D)), dtype)
    new = jnp.asarray(rng.normal(size=(B, D)), dtype)
    lens = jnp.full((B,), n, jnp.int32)
    # 2,047 earlier positions and the new one
    positions = np.stack([np.sort(np.append(
        rng.choice(n, kept - 1, replace=False), n)) for _ in range(B)])
    chosen = np.zeros((B, S), bool)
    np.put_along_axis(chosen, positions, True, axis=1)
    scale = 0.135
    minor = jnp.swapaxes(rows, 1, 2)[None, :, None]        # [1, B, 1, D, S]
    interpret = rehearse

    def masked(q, new, minor, lens, chosen):
        return latent_decode_attention(
            q, new, minor, jnp.int32(0), lens, values=R, scale=scale,
            chosen=chosen, interpret=interpret)

    def gathered(q, rows, positions):
        return gathered_decode_attention(
            q, rows, positions, values=R, scale=scale, interpret=interpret)

    # the gathered form reads the new row from the leaf: put it there
    rows = rows.at[:, n].set(new)
    args_b = (q, new, minor, lens, jnp.asarray(chosen))
    # held as the kernel reads it, 640 wide: the pad is no part of a step
    args_a = (q, jnp.pad(rows, ((0, 0), (0, 0), (0, -D % 128))),
              jnp.asarray(positions))
    if rehearse:
        out_b, out_a = masked(*args_b)[0], gathered(*args_a)
        ms_b = ms_a = None
    else:
        out_b, ms_b = timed(masked, args_b, f"masked_{n}", carried=2)
        out_a, ms_a = timed(gathered, args_a, f"gathered_{n}")
    gap = float(jnp.abs(out_a.astype(jnp.float32)
                        - out_b.astype(jnp.float32)).max())
    return {"cached": n, "slots": B, "kept": kept,
            "masked_ms": ms_b, "gathered_ms": ms_a, "max_gap": gap,
            "masked_bytes": B * (n + 1) * D * 2,
            "gathered_bytes": B * kept * GROUP * (D + -D % 128) * 2}


def main(args):
    if "--rehearse" in args:
        print(json.dumps(one(200, B=2, H=4, R=32, Dr=8, kept=16, S=256,
                             rehearse=True)))
        return
    os.makedirs("chiprun_out", exist_ok=True)
    for n in [int(a) for a in args] or [4096, 12288, 20000, 32768]:
        row = one(n)
        print(json.dumps(row), flush=True)
        with open("chiprun_out/time_selected_read.jsonl", "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
