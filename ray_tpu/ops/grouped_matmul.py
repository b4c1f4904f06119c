"""Grouped matrix products: rows sorted by group, each against its group's
own matrix.

    grouped_dot(lhs [M, K], rhs [G, K, N], group_sizes [G]) -> [M, N]
    out[r] = lhs[r] @ rhs[g]    for the rows r of group g

``jax.lax.ragged_dot``'s contract: the groups' rows follow one another from
row 0, ``group_sizes.sum() <= M``, and what comes back in the rows past the
last group is UNDEFINED (on the chip: whatever the memory held), in the
gradient with respect to ``lhs`` too. Which implementation runs is the
platform's choice (``_impl``): two Pallas TPU kernels of this file on a TPU,
``ragged_dot`` elsewhere, which is also the tests' oracle.

The kernels are the megablox scheme (``jax.experimental.pallas.ops.tpu.
megablox``; the TPU compiler's own ``ragged_dot`` kernel is the same scheme
at one fixed row tile of 512), written here because the three callers want
what the library's does not give: tiles chosen from the call's shape
(``tiles``), a boundary tile masked where it is a boundary and nowhere else
and in the operands' own dtype, no scratch where one step holds the whole
contraction, rows that need not divide the row tile, a kernel ``name`` a
trace's reader can count, and the backward pass under the caller's scope.

- ``_gmm`` (``grouped_matmul``): the rows are cut into tiles of ``tm``. A
  VISIT is one (row tile, group) pair with a row in common; the grid's
  middle axis runs over the visits in row order, a scalar-prefetched table
  says which tile and which group each is, and the weight block's index is
  the visit's group. A group with no rows has no visit: its weights are
  never read, so a stack of every layer's experts ``[L x E, K, N]`` with
  one layer's groups filled costs what that layer's own ``[E, K, N]``
  would, and nobody cuts it out (a copy; ``parallel/moe.py:_experts``).
  Consecutive visits of one group keep its weight block in VMEM. A visit
  stores the rows of ITS group and leaves the tile's others as they are.
  ``transpose_rhs`` reads ``rhs [G, N, K]`` transposed block by block (the
  gradient with respect to ``lhs``): no transposed copy of the weights.
- ``_tgmm`` (``grouped_matmul_dw``): ``out[g] = lhs[rows of g].T @ rhs[rows
  of g]``, [G, K, N], the gradient with respect to ``rhs``. The visits are
  the innermost axis; a float32 block accumulates a group's visits and is
  stored when the group changes. Here a group without rows HAS a visit,
  which stores zeros (an optimizer reads every group's gradient).

Inside a kernel a block's product is a rolled loop over chunks of the
block (``PRODUCT_ELEMENTS``): the compiler unrolls a product whole, and the
code of 88 such kernels in one train step is compile and load time.

Operands reach the MXU as they are, bfloat16 x bfloat16 or float32 x
float32, accumulated in float32; of a mixed pair the float32 side is
rounded to bfloat16 on its way in, which is what XLA's default precision
does with it on a TPU.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What one call's blocks may take of the chip's VMEM (128 MiB on a v5e),
# every operand's block counted twice (the pipeline's two buffers).
VMEM_BLOCKS = 40 << 20
# Elements of the weight-side operand of one product inside a kernel (640 x
# 1024). A block's product is a rolled loop over chunks of the block's
# columns (``grouped_matmul``; of the contraction's rows in
# ``grouped_matmul_dw``), each over the whole of the other extent, so no sum
# is carried from chunk to chunk. The compiler unrolls a product whole: a
# kernel that multiplies [256, 2560] by [2560, 768] in one is 0.4-1.1 MB of
# code, 88 times over in the routed train step, which then took 30 s longer
# to compile and 18 s longer to load from the compile cache, every run (my
# chip runs, PR 44).
PRODUCT_ELEMENTS = 640 * 1024
# Rows up to which a call is bound by the weights it touches, not by the
# MXU: a decode tick's rows (slots x top_k).
FEW_ROWS = 512
# Rows a group (of the buffer's) from which the MXU bounds a call.
MANY_ROWS_A_GROUP = 1024


def _impl() -> str:
    """How a grouped product runs, by the platform alone: the kernels on a
    TPU (one that fails to lower there raises, it never gives way),
    ``ragged_dot`` elsewhere. ``pallas_interpret`` is the tests'."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def sublanes(dtype) -> int:
    """Rows of one tile of ``dtype`` in VMEM: 8 of 32 bits, 16 of 16."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _divisor(n: int, most: int, unit: int = 128) -> int:
    """The largest multiple of ``unit`` that divides ``n`` and is at most
    ``most``; ``n`` itself where it is small enough or has none."""
    if n <= most:
        return n
    for t in range(most - most % unit, 0, -unit):
        if n % t == 0:
            return t
    return n


def tiles(rows: int, K: int, N: int, groups: int, dtype
          ) -> Tuple[int, int, int]:
    """(tm, tk, tn) of ``_gmm`` for ``rows`` x K against ``groups`` matrices
    of K x N that can hold rows (a stack's other layers do not count), from
    the call's static shape alone.

    - The weight block is the whole K x N matrix wherever VMEM takes it:
      one DMA a group, kept across the group's visits, and no accumulator.
    - ``tm`` has three regimes (the kernel bench behind each: PERF.md
      section 6, PR 44). Few rows in all (a decode tick, bound by the
      touched experts' weights: 89-91% of the chip's bytes/s at 16 to 128
      rows a tile alike): 32, so that a tile which crosses ten groups is
      ten passes of 32 rows. Many rows a group (training, 1,536 of them:
      bound by the MXU): 256, where the MXU streams longer between two
      weight loads and the boundary tiles, computed once for each of their
      two groups, are still a sixth of the work (512, the compiler's own
      choice, makes them a third). Between (a prefill chunk, 8 to 256 rows
      a group): 128.
    """
    sub = sublanes(dtype)
    size = jnp.dtype(dtype).itemsize
    if rows <= FEW_ROWS:
        tm = 32
    elif rows // max(groups, 1) >= MANY_ROWS_A_GROUP:
        tm = 256
    else:
        tm = 128
    tm = min(tm, -(-rows // sub) * sub)
    # two buffers of each operand's block, float32 results of the product
    return (tm, *_largest_blocks(K, N, lambda tk, tn: 2 * size * (
        tm * tk + tk * tn) + 12 * tm * tn <= VMEM_BLOCKS))


def _largest_blocks(K: int, N: int, fits) -> Tuple[int, int]:
    """(tk, tn): K x N whole where ``fits(tk, tn)``, else the larger extent
    halved (by whole lane tiles that divide it) until it does."""
    tk, tn = K, N
    while not fits(tk, tn):
        if tn >= tk and _divisor(N, tn // 2) < tn:
            tn = _divisor(N, tn // 2)
        elif _divisor(K, tk // 2) < tk:
            tk = _divisor(K, tk // 2)
        elif _divisor(N, tn // 2) < tn:
            tn = _divisor(N, tn // 2)
        else:
            break
    return tk, tn


def _visits(group_sizes, m: int, tm: int, empty_too: bool):
    """The scalar tables of a call: ``offsets`` [G + 1] (group g's rows are
    ``offsets[g] : offsets[g + 1]``), for visit v its group ``group_ids[v]``
    and row tile ``tile_ids[v]`` ([V], V the most there can be: every tile
    once and one more for each further group), and how many visits there
    are. ``empty_too``: a group without rows gets one visit (of any tile).
    Compares and sums over [V, G]: no scatter, no sort."""
    G = group_sizes.shape[0]
    tiles_m = pl.cdiv(m, tm)
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = jnp.minimum(starts // tm, tiles_m - 1)
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                      1 if empty_too else 0)
    visit_ends = jnp.cumsum(count)
    visit_starts = visit_ends - count
    v = jnp.arange(tiles_m + G - 1, dtype=jnp.int32)[:, None]
    mine = (visit_starts[None] <= v) & (v < visit_ends[None])     # [V, G]
    group_ids = jnp.sum(
        jnp.where(mine, jnp.arange(G, dtype=jnp.int32)[None], 0), axis=1)
    tile_ids = jnp.sum(
        jnp.where(mine, first[None] + v - visit_starts[None], 0), axis=1)
    return offsets, group_ids, tile_ids, visit_ends[-1]


def _operand_dtype(lhs, rhs):
    """What both operands are when they reach the MXU."""
    if lhs.dtype == rhs.dtype:
        return lhs.dtype
    return jnp.bfloat16 if jnp.bfloat16 in (lhs.dtype, rhs.dtype) else (
        jnp.promote_types(lhs.dtype, rhs.dtype))


def _rows_of(offsets, group, tile, tm):
    """Of visit (``tile``, ``group``): whether the whole tile is the group's,
    and the column [tm, 1] of which of its rows are."""
    lo, hi = offsets[group], offsets[group + 1]
    start = tile * tm
    row = start + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (lo <= start) & (start + tm <= hi), (row >= lo) & (row < hi)


def _chunk(n: int, other: int) -> int:
    """How much of an extent ``n`` one product inside a kernel takes, the
    other extent ``other`` taken whole: ``PRODUCT_ELEMENTS`` between them,
    in whole lane tiles that divide ``n``."""
    return _divisor(n, max(PRODUCT_ELEMENTS // other, 128))


def _each(n: int, body) -> None:
    """``body(i)`` for i in 0..n-1, as a loop the compiler keeps rolled."""
    if n == 1:
        body(0)
    else:
        jax.lax.fori_loop(0, n, lambda i, c: (body(i), c)[1], 0)


def _at(i, chunk: int):
    """Where chunk ``i`` starts, told to the compiler as a whole chunk."""
    return i * chunk if isinstance(i, int) else pl.multiple_of(
        i * chunk, chunk)


def _vmem_limit(*blocks) -> int:
    """Two buffers of every block, the float32 product beside them, and
    room for what the compiler keeps."""
    return min(2 * sum(blocks) + (24 << 20), 110 << 20)


@functools.partial(jax.jit, static_argnames=(
    "out_dtype", "transpose_rhs", "live_groups", "tiling", "interpret"))
def _gmm(lhs, rhs, group_sizes, out_dtype, *, transpose_rhs=False,
         live_groups=None, tiling=None, interpret=False):
    """lhs [M, K] x rhs [G, K, N] ([G, N, K] with ``transpose_rhs``) -> [M,
    N] in ``out_dtype``; ``live_groups``: how many of the G can hold rows
    (None: all), for ``tiles``."""
    m, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    G = rhs.shape[0]
    cd = _operand_dtype(lhs, rhs)
    tm, tk, tn = tiling or tiles(m, K, N, live_groups or G, lhs.dtype)
    if K % tk:
        raise ValueError(f"the contraction's tile {tk} does not divide {K}")
    tiles_k, tiles_n = K // tk, pl.cdiv(N, tn)
    offsets, group_ids, tile_ids, visits = _visits(group_sizes, m, tm, False)
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    cn = _chunk(tn, tk)

    def kernel(offsets, group_ids, tile_ids, x, w, out, *acc):
        v, k = pl.program_id(1), pl.program_id(2)
        whole, mine = _rows_of(offsets, group_ids[v], tile_ids[v], tm)

        def columns(jn):
            """Columns ``jn * cn`` on of the block: the product over the
            whole contraction block, no sum to carry from chunk to chunk."""
            at = pl.ds(_at(jn, cn), cn)
            y = jax.lax.dot_general(
                x[...].astype(cd),
                (w[at, :] if transpose_rhs else w[:, at]).astype(cd), dims,
                preferred_element_type=jnp.float32)
            if acc:     # the contraction in several grid steps
                sums = acc[0].at[:, at]

                @pl.when(k > 0)
                def _():
                    sums[...] += y

                @pl.when(k == 0)
                def _():
                    sums[...] = y

                y = sums[...]

            # the rows of this visit's group; the tile's other rows keep
            # what another visit stored, or what the memory held (a select
            # never reads the other side's value into the side it takes)
            @pl.when(whole & (k == tiles_k - 1))
            def _():
                out[:, at] = y.astype(out.dtype)

            @pl.when(jnp.logical_not(whole) & (k == tiles_k - 1))
            def _():
                out[:, at] = jnp.where(mine, y.astype(out.dtype), out[:, at])

        _each(tn // cn, columns)

    def lhs_index(n, v, k, offsets, group_ids, tile_ids):
        return tile_ids[v], k

    def rhs_index(n, v, k, offsets, group_ids, tile_ids):
        return (group_ids[v], n, k) if transpose_rhs else (group_ids[v], k, n)

    def out_index(n, v, k, offsets, group_ids, tile_ids):
        return tile_ids[v], n

    out_size = jnp.dtype(out_dtype).itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles_n, visits, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_index),
                pl.BlockSpec((None, tn, tk) if transpose_rhs
                             else (None, tk, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if tiles_k > 1 else []),
        ),
        out_shape=jax.ShapeDtypeStruct((m, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                tm * tk * lhs.dtype.itemsize, tk * tn * rhs.dtype.itemsize,
                tm * tn * out_size, tm * tn * 4)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * K * N, transcendentals=0,
            bytes_accessed=(m * K * lhs.dtype.itemsize * tiles_n
                            + (live_groups or G) * K * N * rhs.dtype.itemsize
                            + m * N * out_size)),
        name="grouped_matmul",
        interpret=interpret,
    )(offsets, group_ids, tile_ids, lhs, rhs)


def dw_tiles(rows: int, K: int, N: int, groups: int, dtypes
             ) -> Tuple[int, int, int]:
    """(tm, tk, tn) of ``_tgmm``: the float32 block a group's visits add up
    in is K x N, whole where VMEM takes it beside its stored copy, so every
    row is read once; the row tile as ``tiles`` has it."""
    lhs_dtype, rhs_dtype = dtypes
    tm = tiles(rows, K, N, groups, lhs_dtype)[0]
    row_bytes = 2 * tm * jnp.dtype(lhs_dtype).itemsize, (
        2 * tm * jnp.dtype(rhs_dtype).itemsize)
    return (tm, *_largest_blocks(K, N, lambda tk, tn: tk * tn * 12
            + tk * row_bytes[0] + tn * row_bytes[1] <= VMEM_BLOCKS))


@functools.partial(jax.jit, static_argnames=(
    "out_dtype", "tiling", "interpret"))
def _tgmm(lhs, rhs, group_sizes, out_dtype, *, tiling=None, interpret=False):
    """lhs [M, K], rhs [M, N] -> [G, K, N] in ``out_dtype``: group g's
    ``lhs[rows].T @ rhs[rows]``, zeros where it has no rows."""
    m, K = lhs.shape
    N = rhs.shape[1]
    G = group_sizes.shape[0]
    cd = _operand_dtype(lhs, rhs)
    tm, tk, tn = tiling or dw_tiles(m, K, N, G, (lhs.dtype, rhs.dtype))
    tiles_k, tiles_n = pl.cdiv(K, tk), pl.cdiv(N, tn)
    offsets, group_ids, tile_ids, visits = _visits(group_sizes, m, tm, True)
    dims = (((0,), (0,)), ((), ()))

    ck = _chunk(tk, tn)

    def kernel(offsets, group_ids, tile_ids, x, y, out, acc):
        v, last = pl.program_id(2), pl.num_programs(2) - 1
        group = group_ids[v]
        opens = (v == 0) | (group_ids[jnp.maximum(v - 1, 0)] != group)
        closes = (v == last) | (group_ids[jnp.minimum(v + 1, last)] != group)
        whole, mine = _rows_of(offsets, group, tile_ids[v], tm)

        def each_part(body):
            """``body(rows ck * j on of the block)``: lhs's columns."""
            _each(tk // ck, lambda j: body(pl.ds(_at(j, ck), ck)))

        @pl.when(opens)
        def _():
            def zero(part):
                acc[part, :] = jnp.zeros((ck, tn), acc.dtype)

            each_part(zero)

        def add(masked):
            def product(part):
                xs, ys = x[:, part], y[...]
                if masked:
                    xs, ys = jnp.where(mine, xs, 0), jnp.where(mine, ys, 0)
                acc[part, :] += jax.lax.dot_general(
                    xs.astype(cd), ys.astype(cd), dims,
                    preferred_element_type=jnp.float32)

            each_part(product)

        pl.when(whole)(lambda: add(False))
        # a boundary tile: the rows of other groups, and of none (where a
        # forward pass left what the memory held), are zeros on both sides
        pl.when(jnp.logical_not(whole)
                & (offsets[group + 1] > offsets[group]))(lambda: add(True))

        @pl.when(closes)
        def _():
            def store(part):
                out[part, :] = acc[part, :].astype(out.dtype)

            each_part(store)

    def lhs_index(n, k, v, offsets, group_ids, tile_ids):
        return tile_ids[v], k

    def rhs_index(n, k, v, offsets, group_ids, tile_ids):
        return tile_ids[v], n

    def out_index(n, k, v, offsets, group_ids, tile_ids):
        return group_ids[v], k, n

    out_size = jnp.dtype(out_dtype).itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles_n, tiles_k, visits),
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((tm, tn), rhs_index)],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((G, K, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                tm * tk * lhs.dtype.itemsize, tm * tn * rhs.dtype.itemsize,
                tk * tn * out_size, tk * tn * 4)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * K * N, transcendentals=0,
            bytes_accessed=(m * K * lhs.dtype.itemsize * tiles_n
                            + m * N * rhs.dtype.itemsize * tiles_k
                            + G * K * N * out_size)),
        name="grouped_matmul_dw",
        interpret=interpret,
    )(offsets, group_ids, tile_ids, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _kernels(lhs, rhs, group_sizes, out_dtype, live_groups, scope,
             interpret):
    return _gmm(lhs, rhs, group_sizes, out_dtype, live_groups=live_groups,
                interpret=interpret)


def _kernels_fwd(lhs, rhs, group_sizes, out_dtype, live_groups, scope,
                 interpret):
    out = _kernels(lhs, rhs, group_sizes, out_dtype, live_groups, scope,
                   interpret)
    return out, (lhs, rhs, group_sizes)


def _kernels_bwd(out_dtype, live_groups, scope, interpret, kept, ct):
    lhs, rhs, group_sizes = kept
    return *_transposes(lhs, rhs, group_sizes, ct, live_groups, scope,
                        interpret), None


def _transposes(lhs, rhs, group_sizes, ct, live_groups, scope, interpret):
    """The two gradients, each in its primal's dtype straight from the
    float32 sums (``ragged_dot``'s transposes make them in the product's
    result dtype and round to the primal's after: the same one rounding)."""
    # a backward function does not inherit its call site's scope
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        d_lhs = _gmm(ct, rhs, group_sizes, lhs.dtype, transpose_rhs=True,
                     live_groups=live_groups, interpret=interpret)
        d_rhs = _tgmm(lhs, ct, group_sizes, rhs.dtype, interpret=interpret)
    return d_lhs, d_rhs


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def grouped_dot(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                preferred_element_type=None, *,
                live_groups: Optional[int] = None,
                scope: Optional[str] = None) -> jax.Array:
    """``jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=)``
    by the platform's implementation; differentiable in ``lhs`` and ``rhs``
    (reverse mode). ``live_groups``: how many of rhs's groups can hold rows,
    where the caller knows (a stack of layers of which one is this call's).
    ``scope``: the ``jax.named_scope`` the call stands under, opened again
    around the backward pass's kernels."""
    impl = _impl()
    if impl == "xla":
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes,
            preferred_element_type=preferred_element_type)
    out_dtype = jnp.dtype(preferred_element_type
                          or jnp.result_type(lhs.dtype, rhs.dtype))
    return _kernels(lhs, rhs, group_sizes.astype(jnp.int32), out_dtype,
                    live_groups, scope, impl == "pallas_interpret")


def grouped_dot_grads(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                      ct: jax.Array, *, live_groups: Optional[int] = None,
                      scope: Optional[str] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """What ``grouped_dot(lhs, rhs, group_sizes)``'s backward pass makes of
    the cotangent ``ct`` [M, N] of its result: (d_lhs [M, K], d_rhs [G, K,
    N]), each in its primal's dtype, by the platform's implementation. For a
    caller whose own ``custom_vjp`` holds the product and hands it a
    cotangent in another dtype than autodiff would (``parallel/moe.py:
    _down_add``: bfloat16 for a float32 result). The rows of ``ct`` past the
    last group are read by neither; those of d_lhs are UNDEFINED."""
    impl = _impl()
    if impl != "xla":
        return _transposes(lhs, rhs, group_sizes.astype(jnp.int32), ct,
                           live_groups, scope, impl == "pallas_interpret")
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        d_lhs = jax.lax.ragged_dot(
            ct, rhs.swapaxes(1, 2), group_sizes,
            preferred_element_type=jnp.float32)
        d_rhs = jax.lax.ragged_dot_general(
            lhs, ct, group_sizes, jax.lax.RaggedDotDimensionNumbers(
                dot_dimension_numbers=(((0,), (0,)), ((), ())),
                lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
            preferred_element_type=jnp.float32)
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype)
