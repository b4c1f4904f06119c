"""``ops/grouped_matmul.py`` against ``jax.lax.ragged_dot``, the kernels in
interpret mode on the CPU at small shapes, and the tile function at the
shapes the benchmark's three routed cells send."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import grouped_matmul as gm

# the TPU's interpreter with what no kernel wrote reading as NaN: a masked
# store that read such memory into a group's row would show
NAN = pltpu.InterpretParams(uninitialized_memory="nan")

# name -> (rows, group sizes, (tm, tk, tn)[, K, N]); K = 256, N = 128
CASES = {
    "a-group-crosses-row-tiles": (64, [40, 24], (16, 128, 128)),
    "several-groups-in-a-tile": (32, [3, 5, 1, 7, 9, 2, 4, 1], (32, 256, 128)),
    "empty-first-last-between": (64, [0, 0, 21, 0, 0, 30, 13, 0], (16, 128, 128)),
    "rows-past-the-last-group": (64, [10, 0, 20, 3, 0, 0, 9, 0], (16, 256, 128)),
    "stacked-one-layer-filled": (48, [0] * 8 + [5, 0, 11, 2, 0, 7, 1, 6]
                                 + [0] * 8, (16, 256, 128)),
    "rows-do-not-divide-the-tile": (56, [17, 0, 30, 9], (32, 128, 128)),
    # a block's product as a loop over two chunks of its columns (of the
    # contraction's rows in the weights' gradient): ``PRODUCT_ELEMENTS``
    "a-block-in-chunks": (48, [20, 0, 19], (16, 1024, 1280), 1024, 1280),
}
K, N = 256, 128


def _operands(rows, sizes, dtype, seed=0, K=K, N=N):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    lhs = jax.random.normal(keys[0], (rows, K), dtype)
    rhs = (jax.random.normal(keys[1], (len(sizes), K, N)) * 0.1).astype(dtype)
    ct = jax.random.normal(keys[2], (rows, N), dtype)
    return lhs, rhs, ct, jnp.asarray(sizes, jnp.int32)


def _close(got, want, dtype):
    tol = 1e-4 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_are_ragged_dot_and_its_transposes(case, dtype):
    """Forward, the gradient with respect to the rows (the same kernel, the
    weights read transposed) and with respect to the weights (the transposed
    sibling; zeros for a group without rows), with uninitialised memory
    reading as NaN: every row of a group is what ``ragged_dot`` gives and
    finite, whatever lies past the last group."""
    rows, sizes, tiling, K, N = (*CASES[case], 256, 128)[:5]
    tm, tk, tn = tiling
    assert (gm._chunk(N, K) < N) == (case == "a-block-in-chunks")
    lhs, rhs, ct, group_sizes = _operands(rows, sizes, dtype, K=K, N=N)
    filled = sum(sizes)
    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(
            lambda a, b: jax.lax.ragged_dot(a, b, group_sizes), lhs, rhs)
        # a caller's selects keep the rows of no group out of the gradients
        d_lhs, d_rhs = vjp(jnp.where(
            jnp.arange(rows)[:, None] < filled, ct, 0).astype(want.dtype))
    got = gm._gmm(lhs, rhs, group_sizes, dtype, tiling=tiling, interpret=NAN)
    assert got.dtype == dtype and got.shape == (rows, N)
    _close(got[:filled], want[:filled], dtype)
    got_lhs = gm._gmm(ct, rhs, group_sizes, dtype, transpose_rhs=True,
                      tiling=(tm, tn, tk), interpret=NAN)
    _close(got_lhs[:filled], d_lhs[:filled], dtype)
    # what the forward left in the rows of no group (here: NaN) is an
    # operand of the weights' gradient, and must not reach it
    poisoned = jnp.where(jnp.arange(rows)[:, None] < filled, lhs, jnp.nan)
    got_rhs = gm._tgmm(poisoned, ct, group_sizes, dtype,
                       tiling=(tm, tk, tn), interpret=NAN)
    assert got_rhs.shape == rhs.shape
    _close(got_rhs, d_rhs, dtype)
    empty = np.asarray(sizes) == 0
    assert not np.asarray(got_rhs, np.float32)[empty].any()


def test_a_visit_leaves_the_rows_of_other_groups_alone():
    """Two groups in one row tile, several column and contraction tiles:
    each of the tile's visits stores its own group's rows."""
    lhs, rhs, _, sizes = _operands(16, [9, 7], jnp.float32)
    got = gm._gmm(lhs, rhs, sizes, jnp.float32, tiling=(16, 128, 128),
                  interpret=True)
    with jax.default_matmul_precision("highest"):
        _close(got, jax.lax.ragged_dot(lhs, rhs, sizes), jnp.float32)


def test_no_group_has_rows():
    """Nothing to visit: the forward kernel runs no step, the weights'
    gradient is zeros for every group."""
    lhs, rhs, ct, sizes = _operands(32, [0, 0, 0, 0], jnp.float32)
    gm._gmm(lhs, rhs, sizes, jnp.float32, tiling=(16, 256, 128),
            interpret=True)
    d_rhs = gm._tgmm(lhs, ct, sizes, jnp.float32, tiling=(16, 256, 128),
                     interpret=True)
    assert d_rhs.shape == rhs.shape and not np.asarray(d_rhs).any()


@pytest.mark.parametrize("out", [None, jnp.float32], ids=["bf16", "f32-out"])
def test_grouped_dot_is_the_platforms_choice_and_differentiable(
        out, monkeypatch):
    """On the CPU ``grouped_dot`` is ``ragged_dot`` itself. With the kernels
    forced (interpret mode) its value and both gradients are ``ragged_dot``'s
    in dtype and, to the operands' rounding, in value: bfloat16 operands
    with a bfloat16 result, and with a float32 result, whose float32
    cotangent meets the bfloat16 weights in the backward pass."""
    sizes = [0, 13, 0, 20, 9, 0]
    lhs, rhs, ct, group_sizes = _operands(48, sizes, jnp.bfloat16)
    filled = sum(sizes)
    real = (jnp.arange(48) < filled)[:, None]

    def loss(dot):
        def f(a, b):
            y = dot(a, b, group_sizes, preferred_element_type=out)
            return (jnp.where(real, y, 0).astype(jnp.float32)
                    * ct.astype(jnp.float32)).sum()
        return f

    jaxpr = str(jax.make_jaxpr(loss(gm.grouped_dot))(lhs, rhs))
    assert "ragged_dot" in jaxpr and "pallas_call" not in jaxpr
    want, want_grads = jax.value_and_grad(
        loss(jax.lax.ragged_dot), (0, 1))(lhs, rhs)
    monkeypatch.setattr(gm, "_impl", lambda: "pallas_interpret")
    jaxpr = str(jax.make_jaxpr(jax.grad(loss(gm.grouped_dot), (0, 1)))(
        lhs, rhs))
    assert "ragged_dot" not in jaxpr and jaxpr.count("pallas_call") == 3
    got, got_grads = jax.value_and_grad(
        loss(gm.grouped_dot), (0, 1))(lhs, rhs)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-2)
    for g, w in zip(got_grads, want_grads):
        assert g.dtype == w.dtype and g.shape == w.shape
        _close(jnp.where(real, g, 0) if g.ndim == 2 else g,
               jnp.where(real, w, 0) if w.ndim == 2 else w, jnp.bfloat16)


BF16, F32 = jnp.bfloat16, jnp.float32
# (rows, K, N, groups that can hold rows, dtype) -> (tm, tk, tn): what the
# benchmark's routed cells send (PERF.md section 6, PR 44, has the kernel
# bench behind each)
SHAPES = {
    # smallthinker-21b-a3b.train-seq8k: a share's buffer, 16 experts
    "train-up": ((36864, 2560, 768, 16, BF16), (256, 2560, 768)),
    "train-down": ((36864, 768, 2560, 16, BF16), (256, 768, 2560)),
    "train-dh": ((36864, 2560, 768, 16, F32), (256, 2560, 768)),
    # olmoe-1b-7b.serve-assist: 16 slots x 8, 64 experts a layer
    "olmoe-decode-up": ((128, 2048, 1024, 64, BF16), (32, 2048, 1024)),
    "olmoe-decode-down": ((128, 1024, 2048, 64, BF16), (32, 1024, 2048)),
    # trinity-mini.serve-mixed: 32 slots x 8, 128 experts a layer
    "trinity-decode-up": ((256, 2048, 1024, 128, BF16), (32, 2048, 1024)),
    "trinity-decode-down": ((256, 1024, 2048, 128, BF16), (32, 1024, 2048)),
    # prefill buckets: 64 to 2,048 tokens x 8
    "prefill-512": ((512, 2048, 1024, 64, BF16), (32, 2048, 1024)),
    "prefill-1k": ((1024, 2048, 1024, 64, BF16), (128, 2048, 1024)),
    "prefill-4k": ((4096, 2048, 1024, 64, BF16), (128, 2048, 1024)),
    "prefill-16k": ((16384, 2048, 1024, 64, BF16), (128, 2048, 1024)),
    "prefill-16k-trinity": ((16384, 1024, 2048, 128, BF16),
                            (128, 1024, 2048)),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_tiles_follow_the_shape(shape):
    """One function, the call's static shape in, the tiles out: the whole
    weight matrix a block at every one of these shapes (so no accumulator
    and one DMA a group), the row tile by the rows a group gets; and what
    the blocks take stays inside the budget the kernel asks VMEM for."""
    args, want = SHAPES[shape]
    tm, tk, tn = gm.tiles(*args)
    assert (tm, tk, tn) == want
    rows, K, N, _, dtype = args
    assert K % tk == 0 and tm % gm.sublanes(dtype) == 0
    size = jnp.dtype(dtype).itemsize
    assert 2 * size * (tm * tk + tk * tn) + 12 * tm * tn <= gm.VMEM_BLOCKS


@pytest.mark.parametrize("shape, want", [
    ((36864, 2560, 768, 16, (BF16, BF16)), (256, 2560, 768)),
    ((36864, 768, 2560, 16, (BF16, F32)), (256, 768, 2560)),
    ((64, 256, 128, 8, (F32, F32)), (32, 256, 128)),
], ids=["train-dW", "train-dW-out", "toy"])
def test_the_weights_gradient_keeps_a_whole_matrix_in_flight(shape, want):
    assert gm.dw_tiles(*shape) == want


def test_a_matrix_too_large_for_a_block_is_cut_by_whole_divisors():
    tm, tk, tn = gm.tiles(65536, 8192, 8192, 8, BF16)
    assert 8192 % tk == 0 and 8192 % tn == 0 and tk * tn < 8192 * 8192
    assert 2 * 2 * (tm * tk + tk * tn) + 12 * tm * tn <= gm.VMEM_BLOCKS
    with pytest.raises(ValueError, match="does not divide"):
        gm._gmm(jnp.zeros((16, 256)), jnp.zeros((2, 256, 128)),
                jnp.array([8, 8]), jnp.float32, tiling=(16, 96, 128))
