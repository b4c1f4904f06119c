"""A state layer's recurrence (``ops/ssm.py``): the chunked scan, the
convolution and the one-step kernel, each held to the plain path that is its
oracle. The scan is held to the recurrence as it is defined, one token after
another (``ssm.recurrence``); the kernel (``interpret=True``: the TPU's
program on the CPU) to ``ssm_update_xla``, and a slot that does not decode
is compared bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm
from ray_tpu.ops.decode_attention import live_slots
from tests.families import LATE, _scattered

H, P, N = 4, 8, 16


def _inputs(B, T, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(B, T, H, P)), dtype)
    # Mamba-2's initial range and a few times past it
    dt = jnp.asarray(5 * np.exp(rng.uniform(
        np.log(1e-3), np.log(1e-1), (B, T, H))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, T, N)), dtype)
    Cm = jnp.asarray(rng.normal(size=(B, T, N)), dtype)
    state = jnp.asarray(rng.normal(size=(B, H, P, N)), jnp.float32)
    return x, dt, A, Bm, Cm, state


@pytest.mark.parametrize("T, chunk", [(37, 8), (37, 16), (32, 8), (5, 64),
                                      (1, 8)])
def test_chunked_scan_equals_the_recurrence_from_a_given_state(T, chunk):
    """T a multiple of the chunk and not, shorter than one chunk, and one
    token; from a state that is not zero."""
    x, dt, A, Bm, Cm, state = _inputs(2, T)
    want_y, want_s = ssm.recurrence(x, dt, A, Bm, Cm, state)
    y, s = ssm.ssm_scan(x, dt, A, Bm, Cm, state, chunk)
    assert y.shape == (2, T, H, P) and y.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-6, rtol=2e-5)


def test_a_scan_in_two_calls_is_the_scan_in_one():
    x, dt, A, Bm, Cm, state = _inputs(2, 40, seed=1)
    y, s = ssm.ssm_scan(x, dt, A, Bm, Cm, state, 16)
    y1, s1 = ssm.ssm_scan(x[:, :24], dt[:, :24], A, Bm[:, :24], Cm[:, :24],
                          state, 16)
    y2, s2 = ssm.ssm_scan(x[:, 24:], dt[:, 24:], A, Bm[:, 24:], Cm[:, 24:],
                          s1, 16)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, atol=2e-5)
    np.testing.assert_allclose(s2, s, atol=2e-6)
    # and a state that is dropped between the calls shows
    _, dropped = ssm.ssm_scan(x[:, 24:], dt[:, 24:], A, Bm[:, 24:],
                              Cm[:, 24:], jnp.zeros_like(s1), 16)
    assert float(jnp.abs(dropped - s).max()) > 1e-2


def test_a_step_whose_dt_is_zero_leaves_the_state_bit_for_bit():
    """How padding is told: rows 1's last 13 steps are no tokens."""
    x, dt, A, Bm, Cm, state = _inputs(2, 24, seed=2)
    real = jnp.asarray([24, 11])
    masked = jnp.where(jnp.arange(24)[None, :, None] < real[:, None, None],
                       dt, 0.0)
    y, s = ssm.ssm_scan(x, masked, A, Bm, Cm, state, 8)
    want_y, want_s = ssm.recurrence(
        x[1:, :11], dt[1:, :11], A, Bm[1:, :11], Cm[1:, :11], state[1:])
    np.testing.assert_allclose(s[1], want_s[0], atol=2e-6)
    np.testing.assert_allclose(y[1, :11], want_y[0], atol=2e-5)
    # nothing but padding: the state as it came, exactly
    _, same = ssm.ssm_scan(x, jnp.zeros_like(dt), A, Bm, Cm, state, 8)
    assert bool((same == state).all())


def test_scan_in_bfloat16_stays_near_the_float32_recurrence():
    x, dt, A, Bm, Cm, state = _inputs(2, 48, seed=3, dtype=jnp.bfloat16)
    want_y, want_s = ssm.recurrence(x, dt, A, Bm, Cm, state)
    y, s = ssm.ssm_scan(x, dt, A, Bm, Cm, state, 16)
    scale = float(jnp.abs(want_y).max())
    assert float(jnp.abs(y - want_y).max()) < 0.02 * scale
    assert float(jnp.abs(s - want_s).max()) < 0.02 * float(
        jnp.abs(want_s).max())


@pytest.mark.parametrize("real", [None, (9, 4, 0)])
def test_convolution_carries_the_rows_before_the_last_real_token(real):
    rng = np.random.default_rng(4)
    B, T, C, K = 3, 9, 12, 4
    seq = jnp.asarray(rng.normal(size=(B, 2 * T, C)), jnp.float32)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (C, K)), jnp.float32)
    b = jnp.asarray(rng.uniform(-0.5, 0.5, (C,)), jnp.float32)
    zeros = jnp.zeros((B, K - 1, C), jnp.float32)
    # the definition: token t hears tokens t - K + 1 .. t, zeros before
    rows = jnp.pad(seq, ((0, 0), (K - 1, 0), (0, 0)))
    want = jax.nn.silu(b + sum(
        w[:, j] * rows[:, j:j + 2 * T] for j in range(K)))
    first, tail = ssm.conv(seq[:, :T], zeros, w, b)
    np.testing.assert_allclose(first, want[:, :T], atol=1e-6)
    assert bool((tail == seq[:, T - K + 1:T]).all())
    counts = None if real is None else jnp.asarray(real)
    second, after = ssm.conv(seq[:, T:], tail, w, b, counts)
    np.testing.assert_allclose(second, want[:, T:], atol=1e-6)
    both = jnp.concatenate([tail, seq[:, T:]], axis=1)
    for i, n in enumerate(real or (T,) * B):
        # the K - 1 rows before token n: the tail itself where n is 0
        assert bool((after[i] == both[i, n:n + K - 1]).all())


def _step_inputs(B, seed=5):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(B, H, P)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, H))) * 0.1, jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, N)), jnp.float32)
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("live", [
    (True, False, True, True, False), (False,) * 5, (True,) * 5, None,
    (False, False, False, False, True)])
def test_update_kernel_equals_the_xla_step_and_skips_idle_slots(live):
    states = jnp.asarray(np.random.default_rng(6).normal(
        size=(3, 5, H, P, N)), jnp.float32)
    x, dt, A, Bm, Cm = _step_inputs(5)
    mask = None if live is None else jnp.asarray(live)
    want_y, want_s = ssm.ssm_update_xla(states[1], x, dt, A, Bm, Cm, mask)
    y, out = ssm.ssm_update(
        states, jnp.int32(1), x, dt, A, Bm, Cm,
        live=None if live is None else live_slots(mask), interpret=True)
    np.testing.assert_allclose(y, want_y, atol=1e-5)
    np.testing.assert_allclose(out[1], want_s, atol=1e-6)
    # the other layers, and the slots that do not decode: bit for bit
    assert bool((out[0] == states[0]).all() and (out[2] == states[2]).all())
    for b, alive in enumerate(live or ()):
        if not alive:
            assert bool((out[1, b] == states[1, b]).all())
            assert bool((y[b] == 0).all())


@pytest.mark.parametrize("heads", [1, 2, 4])
@pytest.mark.parametrize("count", [
    0, 1, 2, ssm.DEPTH, ssm.DEPTH + 1, None])
def test_update_kernel_walks_the_live_slots_in_pieces(
        count, heads, monkeypatch):
    """Live sets of 0, 1, 2, ``DEPTH``, ``DEPTH + 1`` and all slots, in
    scattered order, a slot in pieces of one, two and all four of its heads
    (4, 2 and 1 pieces: a ring reaches into the next slot, or the one after
    it): the states and rows are the XLA step's, a slot that is not live is
    the bits it was and its row zeros, and so is every other layer."""
    B = ssm.DEPTH + 3
    monkeypatch.setattr(ssm, "PIECE_BYTES", heads * P * N * 4)
    assert ssm.heads_a_piece(H, P * N * 4) == heads
    states = jnp.asarray(np.random.default_rng(6).normal(
        size=(3, B, H, P, N)), jnp.float32)
    x, dt, A, Bm, Cm = _step_inputs(B)
    live, mask = _scattered(count, B, seed=10 + heads)
    want_y, want_s = ssm.ssm_update_xla(states[1], x, dt, A, Bm, Cm, mask)
    y, out = ssm.ssm_update(states, jnp.int32(1), x, dt, A, Bm, Cm,
                            live=live, interpret=LATE)
    np.testing.assert_allclose(y, want_y, atol=1e-5)
    np.testing.assert_allclose(out[1], want_s, atol=1e-6)
    assert np.array_equal(out[0], states[0])
    assert np.array_equal(out[2], states[2])
    idle = ~np.asarray(mask)
    assert np.array_equal(out[1][idle], states[1][idle])
    assert not np.asarray(y)[idle].any()


@pytest.mark.parametrize("piece, depth", [(1, 2), (1, 4), (2, 3), (2, 8),
                                          (4, 4)])
def test_the_walks_grain_and_depth_change_no_bit(piece, depth, monkeypatch):
    """Same bytes moved, same sums, another schedule: whatever the piece
    and the ring, the kernel's states and rows are bit for bit those of the
    walk that moves a slot's state whole, one read ahead and two writes
    behind (what the kernel did before it walked in pieces)."""
    B = 6
    states = jnp.asarray(np.random.default_rng(11).normal(
        size=(2, B, H, P, N)), jnp.float32)
    step = _step_inputs(B, seed=12)
    live, _ = _scattered(4, B, seed=13)

    def run(heads, ring):
        monkeypatch.setattr(ssm, "PIECE_BYTES", heads * P * N * 4)
        monkeypatch.setattr(ssm, "DEPTH", ring)
        return ssm.ssm_update(states, jnp.int32(0), *step, live=live,
                              interpret=LATE)

    want_y, want_s = run(H, 2)
    y, out = run(piece, depth)
    assert np.array_equal(np.asarray(y).view(np.uint32),
                          np.asarray(want_y).view(np.uint32))
    assert np.array_equal(np.asarray(out).view(np.uint32),
                          np.asarray(want_s).view(np.uint32))


def test_heads_a_piece_follows_from_the_states_shape_alone():
    """The largest divisor of the heads whose piece stays within
    ``PIECE_BYTES``: the three cells' states (a slot is one piece in each:
    the longest DMAs measured best), a state of twice Granite's heads, a
    head count that is prime, and a head that is over the bound by itself."""
    assert ssm.PIECE_BYTES == 3 << 20
    assert ssm.heads_a_piece(64, 64 * 128 * 4) == 64     # Granite
    assert ssm.heads_a_piece(32, 128 * 128 * 4) == 32    # Ling
    assert ssm.heads_a_piece(30, 96 * 256 * 4) == 30     # Olmo-Hybrid
    assert ssm.heads_a_piece(128, 64 * 128 * 4) == 64
    assert ssm.heads_a_piece(37, 96 * 256 * 4) == 1
    assert ssm.heads_a_piece(7, 96 * 256 * 4) == 7
    assert ssm.heads_a_piece(8, 2 << 20) == 1
    assert ssm.heads_a_piece(3, 4 << 20) == 1


def test_a_slot_need_not_fit_vmem_whole_but_a_ring_of_heads_must():
    """A slot's state of 4.7 MB (more than the 4 MB the kernel once held
    whole, four times over) goes through in three pieces of three 512 KB
    heads; a head that ``2 x DEPTH`` of do not fit beside each other is
    refused."""
    heads, rows, lanes = 9, 512, 256
    states = jnp.asarray(np.random.default_rng(14).normal(
        size=(1, 2, heads, rows, lanes)), jnp.float32)
    assert states[0, 0].nbytes > 4 << 20
    rng = np.random.default_rng(15)
    x = jnp.asarray(rng.normal(size=(2, heads, rows)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (2, heads)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, (heads,)), jnp.float32)
    Bm, Cm = (jnp.asarray(rng.normal(size=(2, lanes)), jnp.float32)
              for _ in range(2))
    live, mask = _scattered(1, 2, seed=16)
    want_y, want_s = ssm.ssm_update_xla(states[0], x, dt, A, Bm, Cm, mask)
    y, out = ssm.ssm_update(states, jnp.int32(0), x, dt, A, Bm, Cm,
                            live=live, interpret=LATE)
    np.testing.assert_allclose(y, want_y, atol=1e-4)    # sums of 256
    np.testing.assert_allclose(out[0], want_s, atol=1e-6)
    wide = ssm.RING_BYTES // (2 * ssm.DEPTH * 4 * 128) + 8
    with pytest.raises(ValueError, match="goes through VMEM whole"):
        ssm.ssm_update(jnp.zeros((1, 1, 1, wide, 128), jnp.float32),
                       jnp.int32(0), x[:1, :1, :1].repeat(wide, 2),
                       dt[:1, :1], A[:1], Bm[:1, :128], Cm[:1, :128],
                       interpret=True)


def test_one_step_is_the_recurrence_of_one_token():
    x, dt, A, Bm, Cm = _step_inputs(2, seed=7)
    state = jnp.asarray(np.random.default_rng(7).normal(
        size=(2, H, P, N)), jnp.float32)
    want_y, want_s = ssm.recurrence(
        x[:, None], dt[:, None], A, Bm[:, None], Cm[:, None], state)
    y, s = ssm.ssm_update_xla(state, x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, want_y[:, 0], atol=1e-5)
    np.testing.assert_allclose(s, want_s, atol=1e-6)


def test_update_kernel_refuses_a_state_that_is_not_float32():
    x, dt, A, Bm, Cm = _step_inputs(2)
    with pytest.raises(ValueError, match="float32 state"):
        ssm.ssm_update(jnp.zeros((1, 2, H, P, N), jnp.bfloat16),
                       jnp.int32(0), x, dt, A, Bm, Cm, interpret=True)
