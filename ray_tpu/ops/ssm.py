"""A Mamba-2 state layer's recurrence: what a sequence carries from token to
token is a state a head, not a column a position.

A head of ``P`` channels keeps ``S [P, N]``:

    S_t = exp(dt_t A) S_{t-1} + dt_t (x_t outer B_t),    y_t = S_t C_t

with ``dt_t > 0`` and ``A < 0`` a head, ``B_t`` and ``C_t [N]`` shared by the
heads (one group). Before it, ``x``, ``B`` and ``C`` pass a depthwise causal
convolution of ``K`` taps, so a sequence also carries the last ``K - 1`` rows
that entered it (``conv``). Nothing grows with the position.

Three pieces, each with the plain XLA path that is the CPU's and the tests'
oracle:

- ``conv``: the convolution over a block of tokens FROM a given tail TO the
  tail after the block's last REAL token.
- ``ssm_scan`` (a block of tokens: prefill): chunked. Within a chunk of
  ``chunk`` tokens everything is products (the chunk's tokens against each
  other under their decays; the chunk's sum into the state); the state goes
  from chunk to chunk in a short scan. From a given state to the state after
  the last real token: a step whose ``dt`` is 0 decays by ``exp(0) = 1`` and
  adds ``0``, so it leaves the state as it was, bit for bit, and that is how
  padding and a slot that does not decode are told (the caller zeroes their
  ``dt``). XLA on every platform.
- ``ssm_update`` (one token a slot: decode): one Pallas TPU kernel over the
  WHOLE state ``[L, B, H, P, N]``, aliased to its result (an operand of a
  custom call is a whole array: a layer's slice cut by the scan would be
  copied for it). The state stays in HBM; the layer index and the live slots
  ride as scalar-prefetch arguments and steer the kernel's own DMAs. It
  visits the slots it is told decode (``decode_attention.live_slots``), one
  slot's state in flight while the one before is computed on, and no other:
  a slot that is not live starts no DMA in either direction, its state leaves
  the call as it entered and its row of the result is zeros.
  ``ssm_update_xla`` is the same step over one layer's slice.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# of one slot's state in VMEM; two in flight and two on their way back
STATE_BYTES = 4 << 20


def conv(xbc, tail, weight, bias, real=None):
    """xbc [B, T, C] through the depthwise causal convolution ``weight``
    [C, K] + ``bias`` [C] and a SiLU, the ``K - 1`` rows before the block
    taken from ``tail`` [B, K - 1, C] (zeros before a sequence's first
    token) -> (the result [B, T, C] in xbc's dtype, the tail after the
    block's last real token). ``real`` [B]: how many of a row's T tokens are
    tokens (None: all); what follows them does not reach the tail."""
    B, T, C = xbc.shape
    K = weight.shape[-1]
    rows = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    w = weight.astype(jnp.float32)
    out = bias.astype(jnp.float32) + sum(
        w[:, j] * rows[:, j:j + T].astype(jnp.float32) for j in range(K))
    if real is None:
        after = rows[:, T:]
    elif T == 1:            # a decode step: moved on by one row, or not
        after = jnp.where(real[:, None, None] > 0, rows[:, 1:],
                          rows[:, :-1])
    else:
        # token t is row t + K - 1: the K - 1 rows before token ``real``
        after = jax.vmap(lambda r, at: jax.lax.dynamic_slice_in_dim(
            r, at, K - 1, 0))(rows, real.astype(jnp.int32))
    return jax.nn.silu(out).astype(xbc.dtype), after.astype(tail.dtype)


def recurrence(x, dt, A, Bm, Cm, state):
    """The recurrence as it is defined, one token after another: what the
    chunked scan is held to (``tests/test_ssm.py``). x [B, T, H, P], dt
    [B, T, H], A [H], Bm and Cm [B, T, N], state [B, H, P, N]; float32."""
    f32 = jnp.float32

    def step(S, xs):
        x_t, dt_t, b_t, c_t = xs
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return S, jnp.einsum("bhpn,bn->bhp", S, c_t)

    state, y = jax.lax.scan(step, state.astype(f32), tuple(
        jnp.moveaxis(a.astype(f32), 1, 0) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), state


def ssm_scan(x, dt, A, Bm, Cm, state, chunk: int):
    """x [B, T, H, P], dt [B, T, H] float32 (0 at a step that is no token),
    A [H] float32, Bm and Cm [B, T, N], state [B, H, P, N] float32 ->
    (y [B, T, H, P] float32, the state after the block). The products run in
    x's dtype and sum in float32; the decays and the state are float32."""
    B, T, H, P = x.shape
    f32, cdt = jnp.float32, x.dtype
    Q = min(chunk, T)
    pad = -T % Q
    if pad:     # steps that are no token: dt 0 leaves the state alone
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (x, dt, Bm, Cm))
    nc = (T + pad) // Q
    with jax.named_scope("ssm_scan"):
        x, dt, Bm, Cm = (a.reshape(B, nc, Q, *a.shape[2:])
                         for a in (x, dt, Bm, Cm))
        # log of the decay from a chunk's start to each step: [B, nc, H, Q]
        cs = jnp.cumsum(jnp.moveaxis(dt * A, 2, 3), axis=-1)
        xdt = x.astype(f32) * dt[..., None]               # [B, nc, Q, H, P]
        # inside a chunk: step i hears step j <= i through C_i . B_j under
        # the decay between them
        i = jnp.arange(Q)
        seg = cs[..., :, None] - cs[..., None, :]         # [B, nc, H, Q, Q]
        decay = jnp.exp(jnp.where(i[:, None] >= i[None, :], seg, -jnp.inf))
        heard = jnp.einsum("bcin,bcjn->bcij", Cm, Bm,
                           preferred_element_type=f32)
        y = jnp.einsum("bchij,bcjhp->bcihp",
                       (heard[:, :, None] * decay).astype(cdt),
                       xdt.astype(cdt), preferred_element_type=f32)
        # what each chunk adds to the state by its end
        to_end = jnp.exp(cs[..., -1:] - cs)               # [B, nc, H, Q]
        added = jnp.einsum(
            "bcjhp,bcjn->bchpn",
            (xdt * jnp.moveaxis(to_end, 2, 3)[..., None]).astype(cdt), Bm,
            preferred_element_type=f32)

        def carry(S, xs):
            whole, add = xs
            return whole[..., None, None] * S + add, S

        state, entering = jax.lax.scan(
            carry, state.astype(f32),
            (jnp.moveaxis(jnp.exp(cs[..., -1]), 1, 0),
             jnp.moveaxis(added, 1, 0)))
        # what each step hears of the state its chunk started from: the
        # state is the whole history, so this product keeps float32
        y = y + jnp.einsum(
            "bcin,bchpn->bcihp", Cm.astype(f32), jnp.moveaxis(entering, 0, 1),
            precision=jax.lax.Precision.HIGHEST,
        ) * jnp.moveaxis(jnp.exp(cs), 2, 3)[..., None]
    return y.reshape(B, nc * Q, H, P)[:, :T], state


def ssm_update_xla(state, x, dt, A, Bm, Cm, live=None):
    """One step of every slot: state [B, H, P, N] float32, x [B, H, P], dt
    [B, H], A [H], Bm and Cm [B, N] -> (y [B, H, P] float32, state).
    ``live`` [B] bool: the slots that decode (None: every slot); any other
    keeps its state and gets zeros."""
    f32 = jnp.float32
    x, dt, Bm, Cm = (a.astype(f32) for a in (x, dt, Bm, Cm))
    new = (jnp.exp(dt * A)[..., None, None] * state
           + (dt[..., None] * x)[..., None] * Bm[:, None, None, :])
    y = jnp.einsum("bhpn,bn->bhp", new, Cm,
                   precision=jax.lax.Precision.HIGHEST)
    if live is None:
        return y, new
    keep = live[:, None, None]
    return jnp.where(keep, y, 0.0), jnp.where(keep[..., None], new, state)


def _kernel(layer_ref, live_ref, decay_ref, xdt_ref, b_ref, c_ref, s_hbm,
            y_ref, so_hbm, sbuf, obuf, read_sem, write_sem):
    B, _, H = decay_ref.shape
    visits = live_ref[B]    # the live slots' indices, then their count
    layer = layer_ref[0]

    def read(v, buf):
        return pltpu.make_async_copy(
            s_hbm.at[layer, live_ref[v]], sbuf.at[buf], read_sem.at[buf])

    def write(v, buf):
        return pltpu.make_async_copy(
            obuf.at[buf], so_hbm.at[layer, live_ref[v]], write_sem.at[buf])

    def visit(v, _):
        buf = v % 2

        @pl.when(v + 1 < visits)
        def _():            # the next slot's state sets out
            read(v + 1, 1 - buf).start()

        read(v, buf).wait()

        @pl.when(v >= 2)
        def _():            # the state sent back two visits ago
            write(v - 2, buf).wait()

        b = live_ref[v]
        heard, said = b_ref[b], c_ref[b]                  # [1, N]
        for h in range(H):
            # a head's decay and its dt * x lie down the sublanes: [P, 1]
            new = (decay_ref[b, :, h:h + 1] * sbuf[buf, h]
                   + xdt_ref[b, :, h:h + 1] * heard)
            obuf[buf, h] = new
            y_ref[b, :, h:h + 1] = jnp.sum(
                new * said, axis=-1, keepdims=True)
        write(v, buf).start()
        return 0

    # what no visit writes, a slot that is not live, leaves as zeros
    y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(visits > 0)
    def _():
        read(0, 0).start()

    jax.lax.fori_loop(0, visits, visit, 0)
    for back in (2, 1):     # the last two visits' states

        @pl.when(visits >= back)
        def _():
            write(visits - back, (visits - back) % 2).wait()


def ssm_update(states, layer, x, dt, A, Bm, Cm, *, live=None,
               interpret: bool = False):
    """One step of layer ``layer`` of ``states`` [L, B, H, P, N] float32, in
    place: x [B, H, P], dt [B, H], A [H], Bm and Cm [B, N] -> (y [B, H, P]
    float32, states: the operand's own buffer). ``live``
    (``decode_attention.live_slots``' [B + 1]; None: every slot) names the
    slots this holds for: any other slot's state is left as it is and its
    row of ``y`` is zeros."""
    L, B, H, P, N = states.shape
    f32 = jnp.float32
    if states.dtype != f32:
        raise ValueError(f"the kernel steps a float32 state, not "
                         f"{states.dtype}: the XLA path's (ssm_update_xla)")
    if H * P * N * 4 > STATE_BYTES:
        raise ValueError(
            f"a slot's state of {H} x {P} x {N} is held in VMEM whole, "
            f"four at a time: more than {STATE_BYTES} bytes is not")
    if live is None:        # slots 0 .. B - 1, and B of them
        live = jnp.arange(B + 1, dtype=jnp.int32)
    dt = dt.astype(f32)
    # a head's scalars down the sublanes of its [P, N] tile: [B, P, H]
    decay = jnp.broadcast_to(jnp.exp(dt * A)[:, None, :], (B, P, H))
    xdt = jnp.swapaxes(dt[..., None] * x.astype(f32), 1, 2)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    y, states = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[vmem, vmem, vmem, vmem, hbm],
            out_specs=[vmem, hbm],
            scratch_shapes=[
                pltpu.VMEM((2, H, P, N), f32),
                pltpu.VMEM((2, H, P, N), f32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, P, H), f32),
                   jax.ShapeDtypeStruct(states.shape, f32)],
        # operands count from the scalar-prefetch arguments on
        input_output_aliases={6: 1},
        # four states held, and the small operands, whose rows pad to
        # whole tiles
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * H * P * N * 4 + (32 << 20)),
        name="ssm_update",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live.astype(jnp.int32),
      decay, xdt, Bm.astype(f32)[:, None, :], Cm.astype(f32)[:, None, :],
      states)
    return jnp.swapaxes(y, 1, 2), states
