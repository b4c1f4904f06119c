"""The gated delta rule (``ops/delta_rule.py``): the chunked scan and the
one-step kernel, each held to the plain path that is its oracle. The scan is
held to the recurrence as it is defined, one token after another
(``delta_rule.recurrence``); the kernel (``interpret=True``: the TPU's
program on the CPU) to ``delta_update_xla``, and a slot that does not decode
is compared bit for bit. Float32, seeded; each tolerance with its reason."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import delta_rule, ssm
from ray_tpu.ops.decode_attention import live_slots
from tests.families import LATE, _scattered

H, DK, DV = 3, 8, 16


def _inputs(B, T, seed=0, dtype=jnp.float32, decay=(1e-3, 3.0), beta=2.0,
            H=H):
    """Keys and queries of unit length, ``beta`` up to its bound of 2 (past
    1 an eigenvalue of a step turns negative), log decays from -0.001 (a
    step that keeps nearly everything) to -3 (one that keeps a twentieth),
    a state that is not zero."""
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    q = jnp.asarray(unit(rng.normal(size=(B, T, H, DK))) * DK ** -0.5, dtype)
    k = jnp.asarray(unit(rng.normal(size=(B, T, H, DK))), dtype)
    v = jnp.asarray(rng.normal(size=(B, T, H, DV)), dtype)
    g = -jnp.asarray(np.exp(rng.uniform(
        np.log(decay[0]), np.log(decay[1]), (B, T, H))), jnp.float32)
    b = jnp.asarray(rng.uniform(0, beta, (B, T, H)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(B, H, DK, DV)), jnp.float32)
    return q, k, v, g, b, state


@pytest.mark.parametrize("T, chunk", [(37, 8), (37, 16), (32, 8), (5, 64),
                                      (1, 8), (100, 64)])
def test_chunked_scan_equals_the_recurrence_from_a_given_state(T, chunk):
    """T a multiple of the chunk and not, shorter than one chunk, one token,
    and the published chunk of 64; from a state that is not zero."""
    q, k, v, g, b, state = _inputs(2, T)
    want_o, want_s = delta_rule.recurrence(q, k, v, g, b, state)
    o, s = delta_rule.delta_scan(q, k, v, g, b, state, chunk)
    assert o.shape == (2, T, H, DV) and o.dtype == jnp.float32
    # float32 against float32: a chunk's triangular solve and its products
    # sum in another order than the token-by-token recurrence does; 3e-6
    # measured on outputs of up to 4 and states of up to 5
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("decay, beta", [
    ((5.0, 40.0), 2.0),      # alpha from 0.007 down to 4e-18: nothing kept
    ((1e-6, 1e-4), 2.0),     # alpha within 1e-4 of 1: everything kept
    ((1e-3, 3.0), 0.0)])     # nothing ever written: the state only decays
def test_scan_holds_at_decays_near_zero_and_near_one(decay, beta):
    """Every ratio of two decays is the exponential of a difference that is
    <= 0, so a decay that underflows divides nothing; with beta at its
    bound in every step the corrections of a whole chunk are a triangular
    system far from the identity."""
    q, k, v, g, _, state = _inputs(2, 48, seed=1, decay=decay)
    b = jnp.full(g.shape, beta, jnp.float32)
    want_o, want_s = delta_rule.recurrence(q, k, v, g, b, state)
    o, s = delta_rule.delta_scan(q, k, v, g, b, state, 16)
    assert bool(jnp.isfinite(o).all() and jnp.isfinite(s).all())
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("n", [64, 37, 8, 5, 1])
def test_a_chunks_triangular_system_is_inverted_by_halves(n):
    """``(I + A)^-1`` of what a chunk's keys give, ``A = tril(beta K K^T)``:
    keys drawn apart (entries of 0.1), and the worst a prompt can hold, one
    key at every step with beta at its bound (every entry 2: the inverse's
    entries are then 1 and -2 and 2 in turn, and the products give them
    exactly)."""
    rng = np.random.default_rng(n)
    k = rng.normal(size=(3, n, DK))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    apart = np.tril(rng.uniform(0, 2, (3, n, 1)) * (k @ k.swapaxes(1, 2)), -1)
    same = np.tril(np.full((3, n, n), 2.0), -1)
    for A in (apart, same):
        want = np.linalg.inv(np.eye(n) + A)
        got = delta_rule._unit_lower_inverse(jnp.asarray(A, jnp.float32))
        # float32 products against a float64 inverse whose entries stay
        # under 2.5: 5e-7 measured
        assert np.abs(want).max() < 2.5
        np.testing.assert_allclose(got, want, atol=5e-6)


def test_a_scan_in_two_calls_is_the_scan_in_one():
    q, k, v, g, b, state = _inputs(2, 40, seed=2)
    o, s = delta_rule.delta_scan(q, k, v, g, b, state, 16)
    first = [a[:, :24] for a in (q, k, v, g, b)]
    rest = [a[:, 24:] for a in (q, k, v, g, b)]
    o1, s1 = delta_rule.delta_scan(*first, state, 16)
    o2, s2 = delta_rule.delta_scan(*rest, s1, 16)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o, atol=2e-5)
    np.testing.assert_allclose(s2, s, atol=2e-5)
    # and a state that is dropped between the calls shows
    _, dropped = delta_rule.delta_scan(*rest, jnp.zeros_like(s1), 16)
    assert float(jnp.abs(dropped - s).max()) > 1e-2


def test_a_step_whose_gates_are_zero_leaves_the_state_bit_for_bit():
    """How padding is told: row 1's last 13 steps are no tokens (g = 0 and
    beta = 0, whatever their q, k and v)."""
    q, k, v, g, b, state = _inputs(2, 24, seed=3)
    real = jnp.asarray([24, 11])
    token = jnp.arange(24)[None, :, None] < real[:, None, None]
    o, s = delta_rule.delta_scan(
        q, k, v, jnp.where(token, g, 0.0), jnp.where(token, b, 0.0), state, 8)
    want_o, want_s = delta_rule.recurrence(
        *(a[1:, :11] for a in (q, k, v, g, b)), state[1:])
    np.testing.assert_allclose(s[1], want_s[0], atol=2e-5)
    np.testing.assert_allclose(o[1, :11], want_o[0], atol=2e-5)
    # a bucket's padding past the chunk the last token is in: the state the
    # shorter block left, exactly (chunks of nothing multiply by 1, add 0)
    _, short = delta_rule.delta_scan(
        *(a[1:, :16] for a in (q, k, v)),
        *(jnp.where(token, a, 0.0)[1:, :16] for a in (g, b)), state[1:], 8)
    assert bool((short[0] == s[1]).all())
    # nothing but padding: the state as it came, exactly
    zeros = jnp.zeros_like(g)
    _, same = delta_rule.delta_scan(q, k, v, zeros, zeros, state, 8)
    assert bool((same == state).all())
    _, same = delta_rule.delta_update_xla(
        state, q[:, 0], k[:, 0], v[:, 0], zeros[:, 0], zeros[:, 0])
    assert bool((same == state).all())


def test_scan_in_bfloat16_stays_near_the_float32_recurrence():
    q, k, v, g, b, state = _inputs(2, 48, seed=4, dtype=jnp.bfloat16)
    want_o, want_s = delta_rule.recurrence(q, k, v, g, b, state)
    o, s = delta_rule.delta_scan(q, k, v, g, b, state, 16)
    # bf16 products between a chunk's tokens, float32 sums and state: two
    # hundredths of the largest value, as ``ssm_scan``'s
    assert float(jnp.abs(o - want_o).max()) < 0.02 * float(
        jnp.abs(want_o).max())
    assert float(jnp.abs(s - want_s).max()) < 0.02 * float(
        jnp.abs(want_s).max())


def _step_inputs(B, seed=5, H=H):
    q, k, v, g, b, _ = _inputs(B, 1, seed=seed, H=H)
    return tuple(a[:, 0] for a in (q, k, v, g, b))


def test_one_step_is_the_recurrence_of_one_token():
    q, k, v, g, b, state = _inputs(2, 1, seed=6)
    want_o, want_s = delta_rule.recurrence(q, k, v, g, b, state)
    o, s = delta_rule.delta_update_xla(
        state, *(a[:, 0] for a in (q, k, v, g, b)))
    # the same sums in the same order but for the products' precision
    np.testing.assert_allclose(o, want_o[:, 0], atol=1e-5)
    np.testing.assert_allclose(s, want_s, atol=1e-6)


@pytest.mark.parametrize("live", [
    (True, False, True, True, False), (False,) * 5, (True,) * 5, None,
    (False, False, False, False, True)])
def test_update_kernel_equals_the_xla_step_and_skips_idle_slots(live):
    states = jnp.asarray(np.random.default_rng(7).normal(
        size=(3, 5, H, DK, DV)), jnp.float32)
    step = _step_inputs(5)
    mask = None if live is None else jnp.asarray(live)
    want_o, want_s = delta_rule.delta_update_xla(states[1], *step, mask)
    o, out = delta_rule.delta_update(
        states, jnp.int32(1), *step,
        live=None if live is None else live_slots(mask), interpret=True)
    # the kernel folds alpha and beta into the key and the value before the
    # products the XLA step makes after them: float32's rounding, 5e-7
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(out[1], want_s, atol=1e-5)
    # the other layers, and the slots that do not decode: bit for bit
    assert bool((out[0] == states[0]).all() and (out[2] == states[2]).all())
    for b, alive in enumerate(live or ()):
        if not alive:
            assert bool((out[1, b] == states[1, b]).all())
            assert bool((o[b] == 0).all())


@pytest.mark.parametrize("heads, a_piece", [(30, 5), (7, 1), (3, 3)])
@pytest.mark.parametrize("count", [
    0, 1, 2, ssm.DEPTH, ssm.DEPTH + 1, None])
def test_update_kernel_walks_the_live_slots_in_pieces(
        count, heads, a_piece, monkeypatch):
    """Live sets of 0, 1, 2, ``DEPTH``, ``DEPTH + 1`` and all slots, in
    scattered order, with Olmo-Hybrid's 30 heads (a count with no power of
    two in it: six pieces of five), a prime count (one head a piece) and a
    slot that is one piece: the states and rows are the XLA step's, a slot
    that is not live is the bits it was and its row zeros, and so is every
    other layer."""
    B = ssm.DEPTH + 2
    monkeypatch.setattr(ssm, "PIECE_BYTES", 5 * DK * DV * 4)
    assert ssm.heads_a_piece(heads, DK * DV * 4) == a_piece
    states = jnp.asarray(np.random.default_rng(9).normal(
        size=(2, B, heads, DK, DV)), jnp.float32)
    step = _step_inputs(B, seed=10, H=heads)
    live, mask = _scattered(count, B, seed=heads)
    want_o, want_s = delta_rule.delta_update_xla(states[1], *step, mask)
    o, out = delta_rule.delta_update(states, jnp.int32(1), *step, live=live,
                                     interpret=LATE)
    # alpha and beta folded into the key and the value first: 5e-7
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(out[1], want_s, atol=1e-5)
    assert np.array_equal(out[0], states[0])
    idle = ~np.asarray(mask)
    assert np.array_equal(out[1][idle], states[1][idle])
    assert not np.asarray(o)[idle].any()


@pytest.mark.parametrize("piece, depth", [(1, 2), (1, 4), (3, 3), (5, 8),
                                          (15, 4)])
def test_the_walks_grain_and_depth_change_no_bit(piece, depth, monkeypatch):
    """Whatever the piece and the ring, 30 heads' states and rows are bit
    for bit those of the walk that moves a slot's state whole, one read
    ahead and two writes behind (the kernel before it walked in pieces)."""
    B, heads = 5, 30
    states = jnp.asarray(np.random.default_rng(11).normal(
        size=(2, B, heads, DK, DV)), jnp.float32)
    step = _step_inputs(B, seed=12, H=heads)
    live, _ = _scattered(3, B, seed=13)

    def run(a_piece, ring):
        monkeypatch.setattr(ssm, "PIECE_BYTES", a_piece * DK * DV * 4)
        monkeypatch.setattr(ssm, "DEPTH", ring)
        return delta_rule.delta_update(states, jnp.int32(1), *step,
                                       live=live, interpret=LATE)

    want_o, want_s = run(heads, 2)
    o, out = run(piece, depth)
    assert np.array_equal(np.asarray(o).view(np.uint32),
                          np.asarray(want_o).view(np.uint32))
    assert np.array_equal(np.asarray(out).view(np.uint32),
                          np.asarray(want_s).view(np.uint32))


def test_the_cache_holds_values_up_to_a_lane_tile_and_the_rest_stays_zero():
    """``GATED_DELTA`` as ``kv_cache.recur`` calls it, on a state as the
    cache holds it (``held_shape``: Dv 16 -> 128): the scan, the XLA step
    and the kernel read the same state, and the columns past Dv leave every
    one of them as zeros."""
    shape = delta_rule.held_shape(H, DK, DV)
    assert shape == (H, DK, 128) and delta_rule.held_shape(30, 96, 192) == (
        30, 96, 256)
    rng = np.random.default_rng(8)
    B, C = 3, 2 * H * DK + H * DV
    state = jnp.zeros((B, *shape), jnp.float32).at[..., :DV].set(
        rng.normal(size=(B, H, DK, DV)))
    mixed = jnp.asarray(rng.normal(size=(B, 6, C)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.01, 1.0, (B, 6, H)), jnp.float32)
    b = jnp.asarray(rng.uniform(0, 2, (B, 6, H)), jnp.float32)
    rec = delta_rule.GATED_DELTA
    o, after = rec.scan(None, mixed, (g, b), state, 4)
    assert o.shape == (B, 6, H, DV) and after.shape == state.shape
    assert bool((after[..., DV:] == 0).all())
    # six one-token steps, by XLA and by the kernel, are the scan of six
    stepped, states = state, jnp.stack([state * 0, state])
    for t in range(6):
        now = (mixed[:, t], (g[:, t], b[:, t]))
        want, stepped = rec.step(None, stepped, *now, None)
        got, states = rec.kernel(None, states, jnp.int32(1), *now, None, True)
        np.testing.assert_allclose(got, want, atol=1e-5)
        np.testing.assert_allclose(want, o[:, t], atol=2e-5)
    np.testing.assert_allclose(stepped, after, atol=2e-5)
    np.testing.assert_allclose(states[1], after, atol=2e-5)
    assert bool((states[1][..., DV:] == 0).all() and (states[0] == 0).all())


def test_update_kernel_refuses_a_state_that_is_not_float32():
    with pytest.raises(ValueError, match="float32 state"):
        delta_rule.delta_update(
            jnp.zeros((1, 2, H, DK, DV), jnp.bfloat16), jnp.int32(0),
            *_step_inputs(2), interpret=True)


def test_both_kernels_visit_the_slots_through_one_skeleton():
    """``ssm.visit_live`` is the one place a one-token kernel's DMAs are
    written: either recurrence gives it its step and its name."""
    assert delta_rule.GATED_DELTA.scope == "delta"
    assert ssm.MAMBA2.scope == "ssm"
    states = jnp.zeros((1, 2, H, DK, DV), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda s: delta_rule.delta_update(s, jnp.int32(0), *_step_inputs(2)))(
            states))
    assert "delta_update" in text and "ssm_update" not in text
