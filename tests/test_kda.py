"""Kimi Delta Attention's recurrence (``ops/kda.py``): the chunked scan and
the one-step kernel, each held to the plain path that is its oracle. The scan
is held to the recurrence as it is defined, one token after another
(``kda.recurrence``); the kernel (``interpret=True``: the TPU's program on
the CPU) to ``kda_update_xla``, and a slot that does not decode is compared
bit for bit. Float32, seeded; each tolerance with its reason."""
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import delta_rule, kda, ssm
from ray_tpu.ops.decode_attention import live_slots
from tests.families import LATE, _scattered

H, DK, DV = 3, 8, 16
LOWER = -5.0    # the published lower bound of a step's log decay


def _inputs(B, T, seed=0, dtype=jnp.float32, g=None):
    """Keys and queries of unit length, beta in (0, 1), a log decay a key
    CHANNEL from the lower bound (a step that keeps 0.7%) to nearly 0 (or
    ``g`` everywhere), a state that is not zero."""
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    q = jnp.asarray(unit(rng.normal(size=(B, T, H, DK))) * DK ** -0.5, dtype)
    k = jnp.asarray(unit(rng.normal(size=(B, T, H, DK))), dtype)
    v = jnp.asarray(rng.normal(size=(B, T, H, DV)), dtype)
    decay = (LOWER / (1 + np.exp(-rng.normal(0, 3, (B, T, H, DK))))
             if g is None else np.full((B, T, H, DK), g))
    b = jnp.asarray(rng.uniform(0, 1, (B, T, H)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(B, H, DK, DV)), jnp.float32)
    return q, k, v, jnp.asarray(decay, jnp.float32), b, state


@pytest.mark.parametrize("T, chunk", [(37, 8), (37, 16), (64, 32), (5, 64),
                                      (1, 8), (150, 64)])
def test_chunked_scan_equals_the_recurrence_from_a_given_state(T, chunk):
    """T a multiple of the chunk and not, shorter than one chunk, one token,
    a chunk of one sub-chunk and of several (32 = 2 x 16, the published 64
    = 4 x 16); from a state that is not zero."""
    q, k, v, g, b, state = _inputs(2, T)
    want_o, want_s = kda.recurrence(q, k, v, g, b, state)
    o, s = kda.kda_scan(q, k, v, g, b, state, chunk)
    assert o.shape == (2, T, H, DV) and o.dtype == jnp.float32
    # float32 against float32: a chunk's triangular solve and its products
    # sum in another order than the token-by-token recurrence does, and a
    # ratio of decays is the product of two factors: 5.4e-6 measured on
    # outputs of up to 0.65 and states of up to 1.4
    np.testing.assert_allclose(o, want_o, atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(s, want_s, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("g", [LOWER, 0.0, -1e-4])
def test_scan_holds_with_every_decay_at_its_bound_and_at_none(g):
    """Every ``g`` at the lower bound: inside a sub-chunk of 16 a column's
    factor reaches exp(75), which float32 holds, and its row's factor takes
    it back (a whole chunk of 64 as one reference would need exp(315)); a
    row's factor falls to exp(-75) and no further. Every ``g`` at 0: nothing
    decays, and the factors are all 1."""
    q, k, v, g, b, state = _inputs(2, 128, seed=1, g=g)
    want_o, want_s = kda.recurrence(q, k, v, g, b, state)
    o, s = kda.kda_scan(q, k, v, g, b, state, 64)
    assert bool(jnp.isfinite(o).all() and jnp.isfinite(s).all())
    np.testing.assert_allclose(o, want_o, atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(s, want_s, atol=3e-5, rtol=3e-5)


def test_a_decay_a_channel_is_not_its_heads_mean():
    """What tells KDA from the gated delta rule: the same inputs under each
    head's MEAN decay give another state, and ``delta_rule``'s scan is what
    that reads."""
    q, k, v, g, b, state = _inputs(2, 48, seed=2)
    _, s = kda.kda_scan(q, k, v, g, b, state, 16)
    mean = g.mean(-1)
    _, flat = kda.kda_scan(
        q, k, v, jnp.broadcast_to(mean[..., None], g.shape), b, state, 16)
    _, scalar = delta_rule.delta_scan(q, k, v, mean, b, state, 16)
    np.testing.assert_allclose(flat, scalar, atol=3e-5, rtol=3e-5)
    assert float(jnp.abs(s - flat).max()) > 1e-2


def test_a_scan_in_two_calls_is_the_scan_in_one():
    q, k, v, g, b, state = _inputs(2, 40, seed=3)
    o, s = kda.kda_scan(q, k, v, g, b, state, 16)
    first = [a[:, :24] for a in (q, k, v, g, b)]
    rest = [a[:, 24:] for a in (q, k, v, g, b)]
    o1, s1 = kda.kda_scan(*first, state, 16)
    o2, s2 = kda.kda_scan(*rest, s1, 16)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o, atol=3e-5)
    np.testing.assert_allclose(s2, s, atol=3e-5)


def test_a_step_whose_gates_are_zero_leaves_the_state_bit_for_bit():
    """How padding is told: ``g = 0`` and ``beta = 0``, whatever q, k and v:
    in the one-token step, and in a scan whose every step is one."""
    q, k, v, g, b, state = _inputs(2, 24, seed=4)
    zero_g, zero_b = jnp.zeros_like(g), jnp.zeros_like(b)
    _, s = kda.kda_update_xla(state, q[:, 0], k[:, 0], v[:, 0], zero_g[:, 0],
                              zero_b[:, 0])
    assert np.array_equal(s, state)
    _, s = kda.kda_scan(q, k, v, zero_g, zero_b, state, 8)
    assert np.array_equal(s, state)
    # a row whose last 13 steps are no tokens ends where its 11th left it
    real = jnp.asarray([24, 11])
    token = jnp.arange(24)[None, :] < real[:, None]
    o, s = kda.kda_scan(q, k, v, jnp.where(token[..., None, None], g, 0.0),
                        jnp.where(token[..., None], b, 0.0), state, 8)
    want_o, want_s = kda.recurrence(
        *(a[1:, :11] for a in (q, k, v, g, b)), state[1:])
    np.testing.assert_allclose(s[1], want_s[0], atol=3e-5)
    np.testing.assert_allclose(o[1, :11], want_o[0], atol=3e-5)


def test_scan_in_bfloat16_stays_near_the_float32_recurrence():
    """The products between a chunk's tokens run in the activations' dtype:
    a column's factor of up to exp(75) is rounded to bfloat16's eight bits
    beside its row's, so a ratio carries 2^-8 twice; the state and every
    product with it stay float32."""
    q, k, v, g, b, state = _inputs(2, 64, seed=5)
    want_o, want_s = kda.recurrence(q, k, v, g, b, state)
    o, s = kda.kda_scan(*(a.astype(jnp.bfloat16) for a in (q, k, v)), g, b,
                        state, 64)
    assert o.dtype == jnp.float32 and s.dtype == jnp.float32
    # outputs of up to 0.64, states of up to 1.2: 0.0033 and 0.0054 measured
    np.testing.assert_allclose(o, want_o, atol=0.02)
    np.testing.assert_allclose(s, want_s, atol=0.03)


def test_one_step_is_the_recurrence_of_one_token():
    q, k, v, g, b, state = _inputs(3, 1, seed=6)
    want_o, want_s = kda.recurrence(q, k, v, g, b, state)
    o, s = kda.kda_update_xla(state, *(a[:, 0] for a in (q, k, v, g, b)))
    np.testing.assert_allclose(o, want_o[:, 0], atol=1e-6)
    np.testing.assert_allclose(s, want_s, atol=1e-6)


@pytest.mark.parametrize("live", [
    None, [True] * 5, [True, False, True, False, False], [False] * 5])
def test_update_kernel_equals_the_xla_step_and_skips_idle_slots(live):
    """The kernel over layer 1 of a state of three layers, five slots: the
    live slots' states and rows are the XLA step's, every other slot's state
    and every other layer are the bits they were, an idle row is zeros."""
    B, L = 5, 3
    q, k, v, g, b, _ = _inputs(B, 1, seed=7)
    rng = np.random.default_rng(8)
    states = jnp.asarray(rng.normal(size=(L, B, H, DK, DV)), jnp.float32)
    keep = None if live is None else jnp.asarray(live)
    want_o, want_s = kda.kda_update_xla(
        states[1], *(a[:, 0] for a in (q, k, v, g, b)), keep)
    o, new = kda.kda_update(
        states, jnp.int32(1), *(a[:, 0] for a in (q, k, v, g, b)),
        live=None if live is None else live_slots(keep), interpret=True)
    np.testing.assert_allclose(o, want_o, atol=1e-6)
    np.testing.assert_allclose(new[1], want_s, atol=1e-6)
    assert np.array_equal(new[0], states[0])
    assert np.array_equal(new[2], states[2])
    for slot, on in enumerate(live or ()):
        if not on:
            assert np.array_equal(new[1, slot], states[1, slot])
            assert not np.asarray(o[slot]).any()


@pytest.mark.parametrize("a_piece, depth", [(1, ssm.DEPTH), (3, ssm.DEPTH),
                                            (1, 2)])
@pytest.mark.parametrize("count", [
    0, 1, 2, ssm.DEPTH, ssm.DEPTH + 1, None])
def test_update_kernel_walks_the_live_slots_in_pieces(
        count, a_piece, depth, monkeypatch):
    """Live sets of 0, 1, 2, ``DEPTH``, ``DEPTH + 1`` and all slots, in
    scattered order, a slot in three pieces of a head and in one, through
    the ring as it is and through one of two: the states and rows are the
    XLA step's AND bit for bit those of the walk that moves a slot's state
    whole, one read ahead and two writes behind (the kernel before it walked
    in pieces); a slot that is not live is the bits it was and its row
    zeros, and so is the other layer. The TPU interpreter runs a DMA when
    it is WAITED for: a wait that is missing shows as wrong numbers."""
    B = ssm.DEPTH + 2
    q, k, v, g, b, _ = _inputs(B, 1, seed=10)
    step = tuple(a[:, 0] for a in (q, k, v, g, b))
    states = jnp.asarray(np.random.default_rng(11).normal(
        size=(2, B, H, DK, DV)), jnp.float32)
    live, mask = _scattered(count, B, seed=12)
    idle = ~np.asarray(mask)

    def run(heads, ring):
        monkeypatch.setattr(ssm, "PIECE_BYTES", heads * DK * DV * 4)
        monkeypatch.setattr(ssm, "DEPTH", ring)
        assert ssm.heads_a_piece(H, DK * DV * 4) == heads
        return kda.kda_update(states, jnp.int32(0), *step,
                              live=live, interpret=LATE)

    want_o, want_s = kda.kda_update_xla(states[0], *step, mask)
    whole_o, whole_s = run(H, 2)
    o, new = run(a_piece, depth)
    np.testing.assert_allclose(o, want_o, atol=1e-6)
    np.testing.assert_allclose(new[0], want_s, atol=1e-6)
    assert np.array_equal(np.asarray(o).view(np.uint32),
                          np.asarray(whole_o).view(np.uint32))
    assert np.array_equal(np.asarray(new).view(np.uint32),
                          np.asarray(whole_s).view(np.uint32))
    assert np.array_equal(new[1], states[1])
    assert np.array_equal(new[0][idle], states[0][idle])
    assert not np.asarray(o)[idle].any()


def test_the_kernel_steps_a_float32_state_and_refuses_any_other():
    """One skeleton (``ssm.visit_live``) and one step body for both delta
    rules: what the skeleton refuses, it refuses here under this name."""
    q, k, v, g, b, _ = _inputs(2, 1, seed=9)
    states = jnp.zeros((1, 2, H, DK, DV), jnp.bfloat16)
    assert kda.KDA.scope == "kda" and isinstance(kda.KDA, ssm.Recurrence)
    with pytest.raises(ValueError, match="kda_update_xla"):
        kda.kda_update(states, jnp.int32(0),
                       *(a[:, 0] for a in (q, k, v, g, b)), interpret=True)
