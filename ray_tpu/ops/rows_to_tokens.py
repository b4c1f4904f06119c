"""Rows added to their tokens: a buffer of rows sorted by group, each row
added into the row of the result that its token names.

    rows_to_tokens(rows [R, D], token [R], group_sizes [G], T) -> [T, D]
    out[t] = sum of rows[r] over the rows r < group_sizes.sum() with
             token[r] == t

which is ``zeros((T, D)).at[token].add(rows, mode="drop")`` with the rows
past the last group left out. ``rows`` may be several buffers of one shape
(the cotangents of one gather's several readers): the result is that of
their sum, which the kernel never writes out. The groups' rows follow one
another from row 0 (``grouped_matmul``'s contract), and INSIDE a group the
tokens ascend: what a stable sort of (token, group) pairs by group leaves
(``parallel/moe.py:_grouped_share``). A row past the last group is never
read, whatever it holds.

XLA's scatter-add of rows is serial on the chip: it sorts the indices,
permutes the updates into that order and adds row after row (1.7 ms of
permutation and 82 ns a row for 36,864 rows of 2,560 float32; PERF.md
section 6, PR 52), because it cannot know that the rows are G runs already
sorted by destination. With that known nothing has to be sorted or permuted:
for a tile of ``Tt`` consecutive tokens the rows of group g that land in it
are ONE contiguous slice of the buffer. Which implementation runs is the
platform's choice and the call's static shape's (``_impl``, ``engages``):
the Pallas TPU kernel of this file on a TPU where a visit finds rows, the
scatter-add elsewhere, which is also the tests' oracle.

The kernel (``rows_to_tokens``) walks the RESULT in tiles of ``Tt`` tokens,
one grid step a tile, the tile's float32 sums in VMEM. ``bounds`` [tiles +
1, G], made outside over integers alone (``_bounds``: a compare and a sum,
no sort), says where in the buffer group g's run for each tile begins; it
and the rows' tokens are scalar-prefetched. A step copies the first ``C``
rows of each group's run into VMEM, group g's at rows ``g * slot`` on (a
DMA a group and buffer, begun at a whole sublane tile below the run's first
row and clamped inside the buffer: ``slot`` = C + one tile), and the VPU
adds the run's rows one by one, row r into row ``token[r] - tile's first``
of the sums: float32 adds in the buffer's order, so float32 rows give bit
for bit what a serial scatter-add gives. Rows of 16 bits, and several
buffers, are first made one float32 chunk (their sum) in VMEM. A run of
more than C rows takes further rounds of the same; C is chosen from the
shape so that one round is the rule (``tiles``). The copies of the next
round (the next tile's first) are in flight while this one is added. The
sums are rounded once, on the way out.

The other form, a one-hot factor [Tt, G x slot] times the chunk on the MXU
(float32 rows as three exact bfloat16 pieces), was measured beside this one
and is not kept: the call alone, 36,864 rows into 16,384 tokens under 16
groups, 1.448 ms for this form's 0.884 at float32 and 0.762 for 0.684 at
bfloat16 (PERF.md section 6, PR 52: chip call 5). The kernel alone
on the chip beside XLA's scatter-add: ``scripts/time_rows_to_tokens.py``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.grouped_matmul import sublanes

# Tokens of the result one grid step holds (its float32 sums: 5.2 MB at
# 2,560 columns). A visit (one tile's run of one group) costs its copies'
# start and its loop's whether it finds 24 rows or 48: the larger tile is
# the faster (PERF.md section 6, PR 52).
TOKENS_A_TILE = 512
# Rows of the buffer a visit has to find, in the mean, for the kernel to
# run: R / (G x tiles). A visit costs its copies' start and wait whether it
# finds rows or not. Timed against XLA's scatter-add on the chip (PERF.md
# section 6, PR 52; float32 rows of 2,560): at 36 rows a visit (16 groups,
# 36,864 rows into 16,384 tokens) 0.88 ms for 4.81; at 12 (128 groups: 6,144
# rows into 2,048 tokens, and 1,536 into 512) 0.24 for 1.29 and 0.077 for
# 0.099; at 1.5 (128 groups, 192 rows into 64 tokens) 0.028 for 0.015.
ROWS_A_VISIT = 8
# Rows whose tokens the kernel takes into SMEM whole (4 bytes each; the
# bounds beside them are at most (tiles + 1) x G <= R / 4 entries where a
# visit finds ``ROWS_A_VISIT`` rows).
SMEM_TOKENS = 1 << 16
# What the kernel's buffers may take of the chip's VMEM (128 MiB on a v5e).
# Both limits are ones the chip's compiler took and the chip ran: the edges
# of ``engages`` compile in ``tests/test_tpu_compile.py`` and are timed by
# ``scripts/time_rows_to_tokens.py`` (PERF.md section 6, PR 52).
VMEM_BUFFERS = 80 << 20


def _impl() -> str:
    """How rows reach their tokens, by the platform alone: the kernel on a
    TPU (where ``engages`` says so), XLA's scatter-add elsewhere.
    ``pallas_interpret`` is the tests'."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def tiles(R: int, G: int, T: int) -> Tuple[int, int]:
    """(Tt, C): the tokens a grid step holds and the rows of one group's run
    a round copies, from the call's static shape alone. C is the rows a
    visit finds if the buffer is full and the groups even, in whole sublane
    tiles of 32 bits: a share's buffer is 1.5 times what a uniform router
    fills (``moe.HELD_ROWS_FACTOR``), so an even routing's visits hold two
    thirds of C in the mean and a second round is rare (at 16 groups and
    512 tokens: 48 +- 7 rows of 72)."""
    Tt = min(TOKENS_A_TILE, -(-T // 16) * 16)
    visits = G * -(-T // Tt)
    return Tt, 8 * max(-(-R // (8 * visits)), 1)


def engages(R: int, G: int, T: int, D: int, dtype, buffers: int = 1) -> bool:
    """Whether the kernel takes a call of this shape (on a TPU): where a
    visit finds ``ROWS_A_VISIT`` rows in the mean, the tokens fit SMEM and
    the buffers VMEM."""
    Tt, C = tiles(R, G, T)
    return (ROWS_A_VISIT * G * -(-T // Tt) <= R <= SMEM_TOKENS
            and _vmem(G, D, Tt, C, dtype, buffers) <= VMEM_BUFFERS)


def _geometry(G: int, C: int, dtype) -> Tuple[int, int, int]:
    """(align, slot, K): the rows of a sublane tile of ``dtype``, the rows
    of the VMEM buffer a group takes, and the buffer's rows."""
    align = sublanes(dtype)
    slot = -(-C // align) * align + align
    return align, slot, G * slot


def _vmem(G: int, D: int, Tt: int, C: int, dtype, buffers: int = 1) -> int:
    """Bytes of the kernel's buffers: two of each buffer's chunk, a group's
    float32 chunk, the float32 sums and two of the result's block."""
    size = jnp.dtype(dtype).itemsize
    _, slot, K = _geometry(G, C, dtype)
    return (2 * buffers * K * D * size + slot * D * 4 + 3 * Tt * D * 4)


def _bounds(token, sizes, Tt: int, tiles_t: int):
    """[(tiles + 1) * G] int32, tile-major: the first row of group g whose
    token is at or past ``tile * Tt`` (of the last entry a group: its end).
    The key ``g * tiles * Tt + token`` ascends over the rows of the groups,
    so an entry is how many keys lie below its own: a compare and a sum."""
    R, G = token.shape[0], sizes.shape[0]
    span = tiles_t * Tt
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    row = jnp.arange(R, dtype=jnp.int32)
    group = jnp.sum(row[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    # a row past the last group lies below no entry
    key = jnp.where(row < ends[-1], group * span + token.astype(jnp.int32),
                    jnp.iinfo(jnp.int32).max)
    entry = (jnp.arange(tiles_t + 1, dtype=jnp.int32)[:, None] * Tt
             + jnp.arange(G, dtype=jnp.int32)[None, :] * span).reshape(-1)
    return jnp.sum(key[None, :] < entry[:, None], axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "T", "out_dtype", "tiling", "interpret"))
def _kernel(rows, token, sizes, T, out_dtype, *, tiling=None,
            interpret=False):
    """``rows``: a tuple of buffers [R, D] of one dtype."""
    n, dtype = len(rows), rows[0].dtype
    R, D = rows[0].shape
    G = sizes.shape[0]
    Tt, C = tiling or tiles(R, G, T)
    align, slot, K = _geometry(G, C, dtype)
    C = slot - align
    tiles_t = pl.cdiv(T, Tt)
    bounds = _bounds(token, sizes, Tt, tiles_t)
    if R % align or R < slot:   # a buffer the last copy cannot be clamped in
        pad = max(-(-R // align) * align, slot) - R
        rows = tuple(jnp.pad(x, ((0, pad), (0, 0))) for x in rows)
        token = jnp.pad(token, (0, pad))
        R += pad
    f32 = jnp.float32
    # a row of 16 bits cannot be read alone, and several buffers are added
    # once, not a row at a time: a group's chunk is made float32 first
    widened = n > 1 or dtype != f32

    def kernel(bounds, token, *refs):
        rows, (out, buf, wide, acc, sems, done) = refs[:n], refs[n:]
        i, last = pl.program_id(0), pl.num_programs(0) - 1

        def run(tile, g, rnd):
            """Of group g's run for ``tile``, round ``rnd``: its rows [lo,
            hi) and the row the copy starts at."""
            lo = bounds[tile * G + g] + rnd * C
            hi = jnp.minimum(lo + C, bounds[(tile + 1) * G + g])
            start = jnp.minimum(lo // align * align, R - slot)
            return lo, hi, pl.multiple_of(start, align)

        def each_copy(tile, rnd, s, what):
            """``what(copy)`` for every buffer's copy of every group that
            has rows in this round, into buffer ``s``."""
            def group(g, c):
                lo, hi, start = run(tile, g, rnd)
                at = pl.ds(pl.multiple_of(g * slot, align), slot)

                @pl.when(lo < hi)
                def _():
                    for b in range(n):
                        what(pltpu.make_async_copy(
                            rows[b].at[pl.ds(start, slot), :],
                            buf.at[s, b, at, :], sems.at[s]))
                return c

            jax.lax.fori_loop(0, G, group, 0)

        @pl.when(i == 0)
        def _():
            done[0] = 0
            each_copy(0, 0, 0, lambda copy: copy.start())

        rounds = jax.lax.fori_loop(
            0, G, lambda g, most: jnp.maximum(
                most, bounds[(i + 1) * G + g] - bounds[i * G + g]), 0)
        rounds = jnp.maximum((rounds + C - 1) // C, 1)
        before = done[0]
        acc[...] = jnp.zeros(acc.shape, f32)

        def one_round(j, c):
            s = (before + j) % 2

            @pl.when(j + 1 < rounds)
            def _():
                each_copy(i, j + 1, 1 - s, lambda copy: copy.start())

            @pl.when((j + 1 == rounds) & (i < last))
            def _():
                each_copy(i + 1, 0, 1 - s, lambda copy: copy.start())

            each_copy(i, j, s, lambda copy: copy.wait())

            def group(g, c):
                lo, hi, start = run(i, g, j)
                at = pl.ds(pl.multiple_of(g * slot, align), slot)
                if widened:
                    @pl.when(lo < hi)
                    def _():
                        chunk = buf[s, 0, at, :].astype(f32)
                        for b in range(1, n):
                            chunk = chunk + buf[s, b, at, :].astype(f32)
                        wide[...] = chunk

                chunk, first = (wide, start) if widened else (
                    buf.at[s, 0], start - g * slot)

                def row(r, c):
                    acc[pl.ds(token[r] - i * Tt, 1), :] += chunk[
                        pl.ds(r - first, 1), :]
                    return c

                jax.lax.fori_loop(lo, jnp.maximum(hi, lo), row, 0)
                return c

            jax.lax.fori_loop(0, G, group, 0)
            return c

        jax.lax.fori_loop(0, rounds, one_round, 0)
        done[0] = before + rounds
        out[...] = acc[...].astype(out.dtype)

    size = dtype.itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles_t,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
            out_specs=pl.BlockSpec((Tt, D), lambda i, bounds, token: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, n, K, D), dtype),
                pltpu.VMEM((slot if widened else 8, D), f32),
                pltpu.VMEM((Tt, D), f32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(_vmem(G, D, Tt, C, dtype, n) + (16 << 20),
                                 110 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=n * R * D, transcendentals=0,
            bytes_accessed=n * R * D * size + T * D * jnp.dtype(
                out_dtype).itemsize),
        name="rows_to_tokens",
        interpret=interpret,
    )(bounds, token.astype(jnp.int32), *rows)


def rows_to_tokens(rows, token: jax.Array, group_sizes: jax.Array, T: int,
                   out_dtype=None) -> jax.Array:
    """rows [R, D] in groups of ``group_sizes`` [G] rows from row 0, inside
    a group in the order of their ``token`` [R] -> [T, D] in ``out_dtype``
    (None: the rows'): row r < ``group_sizes.sum()`` added into row
    ``token[r]`` of the result. A row past the last group is added nowhere,
    whatever it and its token hold. ``rows`` may be a tuple of such buffers
    of one shape and dtype: their sum's result. By the platform's
    implementation and the shape's (``_impl``, ``engages``): the kernel adds
    in float32, the buffers too, and rounds once; XLA's scatter-add adds in
    the rows' dtype what ``sum(rows)`` made in it."""
    rows = tuple(rows) if isinstance(rows, (tuple, list)) else (rows,)
    R, D = rows[0].shape
    dtype = rows[0].dtype
    out_dtype = jnp.dtype(out_dtype or dtype)
    impl = _impl()
    if impl == "xla" or not engages(R, group_sizes.shape[0], T, D, dtype,
                                    len(rows)):
        index = jnp.where(jnp.arange(R) < group_sizes.sum(), token, T)
        return jnp.zeros((T, D), dtype).at[index].add(
            sum(rows[1:], rows[0]), mode="drop").astype(out_dtype)
    return _kernel(rows, token, group_sizes, T, out_dtype,
                   interpret=impl == "pallas_interpret")
