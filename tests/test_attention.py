"""Attention kernel + sequence parallelism tests (8-device CPU mesh)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (
    attention_xla, flash_attention, flash_block_counts, with_shared,
)
from ray_tpu.parallel.mesh import MeshConfig
from ray_tpu.parallel.ring_attention import ring_attention, ulysses_attention
from jax.sharding import NamedSharding, PartitionSpec as P


def _make_qkv(B=2, T=128, H=4, D=32, dtype=jnp.float32, seed=0):
    """Heads-major, [B, H, T, D], as the attention ops take them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, T, D), dtype)
    k = jax.random.normal(ks[1], (B, H, T, D), dtype)
    v = jax.random.normal(ks[2], (B, H, T, D), dtype)
    return q, k, v


def _swapped(*arrays):
    """[B, H, T, D] <-> [B, T, H, D]: the order the sequence-parallel
    attentions keep, whose shards are of the sequence."""
    return tuple(a.swapaxes(1, 2) for a in arrays)


def test_flash_matches_xla_causal():
    q, k, v = _make_qkv()
    ref = attention_xla(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, 64, 64, True)  # interpret mode
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_matches_xla_noncausal():
    q, k, v = _make_qkv(T=64)
    ref = attention_xla(q, k, v, causal=False)
    out = flash_attention(q, k, v, False, 32, 32, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_grad_matches_xla():
    q, k, v = _make_qkv(T=64)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, True, 32, 32, True).sum()

    def loss_xla(q, k, v):
        return attention_xla(q, k, v, causal=True).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    mesh = MeshConfig(data=1, seq=4).build(jax.devices()[:4])
    q, k, v = _make_qkv(B=2, T=128, H=4, D=16)
    spec = P(None, "seq", None, None)
    sharding = NamedSharding(mesh, spec)
    qs, ks, vs = (jax.device_put(x, sharding) for x in _swapped(q, k, v))
    out = ring_attention(qs, ks, vs, mesh=mesh, axis="seq", causal=causal,
                         qkv_spec=spec)
    (ref,) = _swapped(attention_xla(q, k, v, causal=causal))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_grads():
    mesh = MeshConfig(data=1, seq=4).build(jax.devices()[:4])
    q, k, v = _swapped(*_make_qkv(B=1, T=64, H=2, D=8))
    spec = P(None, "seq", None, None)

    def loss_ring(q, k, v):
        return ring_attention(q, k, v, mesh=mesh, axis="seq", causal=True,
                              qkv_spec=spec).sum()

    def loss_ref(q, k, v):
        return attention_xla(*_swapped(q, k, v), causal=True).sum()

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


def test_ulysses_matches_dense():
    mesh = MeshConfig(data=1, seq=4).build(jax.devices()[:4])
    q, k, v = _make_qkv(B=2, T=128, H=4, D=16)
    spec = P(None, "seq", None, None)
    out = ulysses_attention(*_swapped(q, k, v), mesh=mesh, axis="seq",
                            causal=True, qkv_spec=spec)
    (ref,) = _swapped(attention_xla(q, k, v, causal=True))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_gqa_xla():
    q, _, _ = _make_qkv(H=8)
    _, k, v = _make_qkv(H=2, seed=1)
    out = attention_xla(q, k, v, causal=True)
    assert out.shape == q.shape


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("T", [96, 1000])
@pytest.mark.parametrize("block", [64, None], ids=["b64", "chosen"])
def test_flash_ragged_seq_len(causal, T, block):
    """Seq lengths not divisible by the block size (regression: the kernel's
    clamped dynamic slice silently re-read earlier K rows), at a given block
    and at the one the kernel chooses from the length."""
    q, k, v = _make_qkv(B=1, T=T, H=2, D=16)
    out = flash_attention(q, k, v, causal, block, block, True)
    ref = attention_xla(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("T,block,D,H,Hkv", [
    (100, 64, 32, 4, 2),       # ragged, GQA, small blocks
    (1024, 512, 64, 2, 2),     # two blocks a side, the diagonal crossed twice
    (1024, 256, 64, 2, 1),     # plain blocks under the diagonal, GQA
    (1024, None, 64, 1, 1),    # the training cells' call: one block, strips
    (1536, 512, 128, 1, 1),    # three blocks, D = 128 (scale not folded)
    (1536, 256, 64, 2, 1),
    (1000, 256, 64, 2, 1),     # ragged: the padded keys in the last block
    (1000, None, 128, 1, 1),
    # keys and values of two widths (latent attention's up-projected heads:
    # 192 / 128): a length no block divides, one block, and a tiny pair
    (300, 128, (192, 128), 2, 2),
    (256, None, (192, 128), 1, 1),
    (100, 64, (24, 16), 2, 1),
    (200, None, (16, 24), 1, 1),   # the values the wider
], ids=str)
def test_flash_bwd_kernel_gqa_and_ragged(causal, T, block, D, H, Hkv):
    """The ONE Pallas backward kernel (dq, dk, dv from one pass): GQA
    head-group reduction, pad-row masking (q rows past seq end must
    contribute nothing to dk/dv), several blocks with a crossed diagonal,
    the blocks the kernel chooses itself; ``D`` a pair: keys ``D[0]`` and
    values ``D[1]`` wide in one call (scores from the keys' width, the
    result, its cotangent and dv from the values')."""
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 4)
    B = 1
    D, Dv = D if isinstance(D, tuple) else (D, D)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, T, Dv), jnp.float32)
    g = jax.random.normal(ks[3], (B, H, T, Dv), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal, block, block, True)),
        np.asarray(attention_xla(q, k, v, causal=causal)),
        atol=2e-5, rtol=2e-5)

    def loss_flash(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, causal, block, block, True),
                        g)

    def loss_xla(q, k, v):
        return jnp.vdot(attention_xla(q, k, v, causal=causal), g)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("T,block,causal,visited,masked,fwd,bwd", [
    # T = 1024 causal: 0.5625 T^2 in the backward (128-wide strips of the
    # diagonal block), 0.75 T^2 in the forward (512-wide), whatever the block
    (1024, None, True, 1, 1, 0.75, 0.5625),
    (1024, 512, True, 3, 2, 0.75, 0.5625),
    (1024, 256, True, 10, 4, 0.625, 0.5625),
    (1024, 512, False, 4, 0, 1.0, 1.0),
    # ragged: padded to 1024; not causal, the last key block alone is masked
    (1000, None, True, 1, 1, 0.75, 0.5625),
    (1000, 256, False, 16, 4, 1.0, 1.0),
    (1536, 512, True, 6, 3, 1.5, 1.21875),
], ids=str)
def test_flash_block_counts(T, block, causal, visited, masked, fwd, bwd):
    """The work the kernels do, from the shapes alone: block pairs visited,
    those on the masked path (the diagonal's, or the padded tail's), and the
    score elements computed, as shares of 1024^2. No width is asked for: the
    counts are those of a call whose values are narrower than its keys
    too."""
    got = flash_block_counts(T, T, block, block, causal)
    assert got == {"visited": visited, "masked": masked,
                   "elements_fwd": int(fwd * 1024 ** 2),
                   "elements_bwd": int(bwd * 1024 ** 2)}


def test_auto_is_decided_by_platform_and_never_falls_back(monkeypatch):
    """``auto`` is flash on tpu and xla elsewhere, by platform alone. The
    platform is decided here: told it is on a TPU, the dispatcher lowers the
    Mosaic kernel — which this CPU backend refuses — and the refusal must
    surface, not turn into XLA attention."""
    from ray_tpu.ops.attention import attention

    q, k, v = _make_qkv(B=1, T=128, H=1, D=128)
    ref = attention_xla(q, k, v, causal=True)
    out = attention(q, k, v, causal=True, impl="auto")  # cpu -> xla
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(Exception) as err:
        jax.block_until_ready(attention(q, k, v, causal=True, impl="auto"))
    assert not isinstance(err.value, AssertionError)


@pytest.mark.parametrize("axes", [
    {"data": 2, "fsdp": 2, "tensor": 2},
    {"data": -1},
])
def test_flash_under_mesh_runs_per_shard(axes):
    """On a mesh of several devices the kernel runs under shard_map over
    batch and heads (GSPMD cannot partition a Mosaic call): same values and
    grads as dense attention, inside a sharded jit."""
    from ray_tpu.ops.attention import attention

    mesh = MeshConfig(**axes).build()
    q, k, v = _make_qkv(B=8, T=64, H=4, D=32)
    spec = NamedSharding(mesh, P(("data", "fsdp"), "tensor", None, None))
    q, k, v = (jax.device_put(x, spec) for x in (q, k, v))

    def loss(impl, q, k, v):
        out = attention(q, k, v, causal=True, impl=impl, mesh=mesh,
                        block_q=32, block_k=32)
        return (out * out).sum(), out

    grad = lambda impl: jax.jit(  # noqa: E731
        jax.value_and_grad(lambda *a: loss(impl, *a), argnums=(0, 1, 2),
                           has_aux=True)
    )
    ((_, out), g_flash) = grad("flash_interpret")(q, k, v)
    ((_, ref), g_xla) = grad("xla")(q, k, v)
    assert out.sharding.is_equivalent_to(spec, out.ndim)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(g_flash, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def _window_oracle(q, k, v, window):
    """``decoder._window_attention`` over k and v spread to the query heads:
    the band mask in plain XLA that the kernels replace on the chip."""
    from ray_tpu.models.decoder import _window_attention

    rep = q.shape[1] // k.shape[1]
    return _window_attention(
        q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1), window)


@pytest.mark.parametrize("T,block,window,H,Hkv,D", [
    (384, 128, 200, 7, 1, 32),   # G = 7; the window is no multiple of the
    #                              block, T above it: edge blocks and plain
    (384, 128, 256, 7, 1, 32),   # a multiple of the block: the two
    #                              triangles of one block
    (256, 128, 256, 14, 2, 32),  # T at the window: nothing is left out
    (200, 128, 256, 7, 1, 32),   # T below the window, ragged
    (330, 128, 100, 7, 1, 32),   # a window shorter than the block, ragged:
    #                              the diagonal's block is cut by it too
    (1536, 512, 600, 2, 1, 128),  # D = 128, the strips of a square call
    (640, None, 130, 4, 2, 64),  # the blocks the kernel chooses itself
], ids=str)
def test_flash_window_matches_the_band_mask(T, block, window, H, Hkv, D):
    """The window in the flash kernels, forward and backward, against
    ``_window_attention``: float32 on both sides, so the two differ in the
    order of their sums (measured 3e-6 forward, 2e-5 on the gradients; a
    window off by one position reads 1e-2 and more)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (2, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (2, Hkv, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (2, Hkv, T, D), jnp.float32)
    g = jax.random.normal(ks[3], (2, H, T, D), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, block, block, True, window)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)),
        np.asarray(_window_oracle(q, k, v, window)), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(attention_xla(q, k, v, window=window)),
        np.asarray(_window_oracle(q, k, v, window)), atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.vdot(flash(*a), g), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.vdot(_window_oracle(*a, window), g),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("T,block,window,visited,masked,fwd,bwd", [
    # 8192 tokens, blocks of 1024, a window of 4096: a query block past the
    # window visits the edge's block (masked, whole), three plain ones and
    # the diagonal's: 36 - 6 = 30 of the causal 36, 8 + 4 masked
    (8192, None, 4096, 30, 12, 22 + 8 * 0.75, 22 + 8 * 0.5625),
    (8192, None, None, 36, 8, 28 + 8 * 0.75, 28 + 8 * 0.5625),
    (8192, None, 8192, 36, 8, 28 + 8 * 0.75, 28 + 8 * 0.5625),
    # a window that is no multiple of the block: two edge blocks a row and
    # not one plain block
    (4096, 1024, 1500, 9, 9, 5 + 4 * 0.75, 5 + 4 * 0.5625),
], ids=str)
def test_flash_block_counts_with_a_window(T, block, window, visited, masked,
                                          fwd, bwd):
    got = flash_block_counts(T, T, block, block, True, window)
    assert got == {"visited": visited, "masked": masked,
                   "elements_fwd": int(fwd * 1024 ** 2),
                   "elements_bwd": int(bwd * 1024 ** 2)}


def test_a_window_is_causal_self_attention():
    q, k, v = _make_qkv(T=128)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, None, None, True, 64)
    with pytest.raises(ValueError, match="self-attention"):
        flash_attention(q[:, :, :64], k, v, True, None, None, True, 32)


@pytest.mark.parametrize("T,block,widths,H,window", [
    (300, 128, (128, 64, 128), 2, None),   # the published widths, ragged
    (200, 64, (16, 8, 16), 3, None),       # toy widths, three heads a row
    (200, 64, (16, 8, 24), 3, 50),         # a window, the values the wider
    (256, None, (16, 8, 16), 2, None),     # one block, the diagonal's strips
    (200, (64, 128), (16, 8, 16), 3, 120),  # blocks of two sizes, a window
], ids=str)
def test_flash_with_a_shared_key_as_an_operand(T, block, widths, H, window):
    """Latent attention's calls with the rotated key as an operand of its
    own, [B, T, Dr], which every head of a batch row reads where it lies,
    against ``attention_xla`` on the keys with it repeated into every head
    and concatenated: the result and all four gradients, the shared key's
    summed over the heads inside the backward call, in float32 through the
    interpreter at lengths no block divides (measured 3e-6 on the result and
    on each gradient)."""
    Dn, Dr, Dv = widths
    bq, bk = block if isinstance(block, tuple) else (block, block)
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(ks[0], (2, H, T, Dn + Dr), jnp.float32)
    k = jax.random.normal(ks[1], (2, H, T, Dn), jnp.float32)
    v = jax.random.normal(ks[2], (2, H, T, Dv), jnp.float32)
    shared = jax.random.normal(ks[3], (2, T, Dr), jnp.float32)
    g = jax.random.normal(ks[4], (2, H, T, Dv), jnp.float32)

    def flash(q, k, v, shared):
        return flash_attention(q, k, v, True, bq, bk, True, window, shared)

    def plain(q, k, v, shared):
        return attention_xla(q, with_shared(k, shared), v, window=window)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v, shared)), np.asarray(plain(q, k, v, shared)),
        atol=2e-5, rtol=2e-5)
    got, want = (
        jax.grad(lambda *a: jnp.vdot(f(*a), g), argnums=(0, 1, 2, 3))(
            q, k, v, shared) for f in (flash, plain))
    assert got[3].shape == shared.shape
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)
    # and the shared key is something: without it the scores are others
    assert float(jnp.abs(
        flash(q, k, v, shared) - flash(q, k, v, 0 * shared)).max()) > 1e-2


@pytest.mark.parametrize("shape", [(2, 40, 64), (2, 3, 40, 64), (1, 7, 8)],
                         ids=str)
def test_rope_on_the_lanes_is_the_interleaved_rotation(shape):
    """The full forward's rotation (a roll each way and a select by parity,
    on [B, T, D] or heads-major [B, H, T, D]) against the cached forward's
    (pairs as a [.., D / 2, 2] view, on [B, T, .., D]) to float32 rounding,
    and its gradient with it."""
    from ray_tpu.models.bailing_hybrid import _rope_interleaved, _rope_lanes

    B, T = shape[0], shape[-2]
    x = jax.random.normal(jax.random.PRNGKey(9), shape, jnp.float32)
    pos = jnp.arange(T)[None] + jnp.asarray([[5], [300]])[:B]
    swap = (lambda a: a.swapaxes(1, 2)) if x.ndim == 4 else (lambda a: a)

    def lanes(x):
        return _rope_lanes(x, pos, 32e6)

    def pairs(x):
        return swap(_rope_interleaved(swap(x), pos, 32e6))

    np.testing.assert_allclose(np.asarray(lanes(x)), np.asarray(pairs(x)),
                               atol=1e-6, rtol=1e-6)
    g = jax.random.normal(jax.random.PRNGKey(10), shape, jnp.float32)
    np.testing.assert_allclose(
        *(np.asarray(jax.grad(lambda x: jnp.vdot(f(x), g))(x))
          for f in (lanes, pairs)), atol=1e-6, rtol=1e-6)
    assert lanes(x.astype(jnp.bfloat16)).dtype == jnp.bfloat16


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
def test_rope_by_halves_on_the_lanes_is_the_cached_forwards(dtype):
    """llama's rotation (channel i with i + D / 2) as the full forward
    writes it, heads-major and whole rows at a time, against the cached
    forward's slices and concatenation: float32 rounding in float32, one
    rounding of the result in bfloat16 (the cached form rounds cos, sin and
    each product)."""
    from ray_tpu.models.llama import _rope

    x = jax.random.normal(jax.random.PRNGKey(11), (2, 40, 3, 64), dtype)
    pos = jnp.arange(40)[None] + jnp.asarray([[0], [1000]])
    got = _rope(x.swapaxes(1, 2), pos, 1e4, heads_major=True).swapaxes(1, 2)
    want = _rope(x, pos, 1e4)
    assert got.dtype == dtype
    tol = 2e-6 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=0)
