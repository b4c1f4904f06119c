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
  walks the slots it is told decode (``decode_attention.live_slots``) in
  PIECES, a run of whole heads of one slot's state each (``heads_a_piece``:
  from the state's shape alone), slot by slot and within a slot piece by
  piece, through a ring of ``DEPTH`` pieces each way: the next pieces on
  their way in and the last ones on their way back while one is computed
  on. A slot that is not live starts no DMA in either direction, its state
  leaves the call as it entered and its row of the result is zeros.
  ``ssm_update_xla`` is the same step over one layer's slice. The kernel's
  way through the pieces (``visit_live``) is any one-token step's; Mamba-2's
  own is ``_step``, and ``ops/delta_rule.py`` gives it another (which
  ``ops/kda.py`` runs with a decay a channel).

``MAMBA2`` is the three as ``models/kv_cache.py:recur`` takes a kind of state
layer's recurrence (``Recurrence``): from what left the convolution, the
per-step gate and the layer's own ``A_log`` and ``D``.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# of a piece, a run of whole heads of one live slot's state: what one DMA
# moves and one turn of the kernel's walk computes on (``heads_a_piece``).
# On the v5e (PR 54, ``scripts/time_state_update.py``) a slot's reads alone
# run at 737 GB/s, its writes alone at 646, both together at the sum of the
# two times and 3-6% over it: reads and writes gain nothing from running
# together, only the body hides under them, and the fewer and longer the
# DMAs the nearer to the sum. So a piece is a slot wherever a slot is within
# this (2 MB in Granite and Ling, 2.95 in Olmo-Hybrid); half a slot a piece
# costs a walk of two and more slots 1-2.5% and saves a lone slot's call,
# whose body then hides too, a quarter.
PIECE_BYTES = 3 << 20
# pieces in the ring each way: reads set out DEPTH - 1 pieces ahead of the
# one computed on, a write is waited for DEPTH pieces after it set out (one
# read ahead leaves HBM waiting for a body: 2 loses 2-6% to 3; 4 and 6 read
# as 3)
DEPTH = 3
# of the two rings together: half of what VMEM a v5e core has
RING_BYTES = 64 << 20


def conv(xbc, tail, weight, bias, real=None):
    """xbc [B, T, C] through the depthwise causal convolution ``weight``
    [C, K] + ``bias`` [C] (None: none) and a SiLU, the ``K - 1`` rows before the block
    taken from ``tail`` [B, K - 1, C] (zeros before a sequence's first
    token) -> (the result [B, T, C] in xbc's dtype, the tail after the
    block's last real token). ``real`` [B]: how many of a row's T tokens are
    tokens (None: all); what follows them does not reach the tail."""
    B, T, C = xbc.shape
    K = weight.shape[-1]
    rows = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    w = weight.astype(jnp.float32)
    out = sum(w[:, j] * rows[:, j:j + T].astype(jnp.float32)
              for j in range(K))
    if bias is not None:
        out = bias.astype(jnp.float32) + out
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


def heads_a_piece(heads: int, head_bytes: int) -> int:
    """Whole heads of a slot's state that make one piece of the kernel's
    walk: the largest divisor of ``heads`` whose piece stays within
    ``PIECE_BYTES`` (one head where none does)."""
    return max(hp for hp in range(1, heads + 1) if heads % hp == 0
               and (hp == 1 or hp * head_bytes <= PIECE_BYTES))


def _visiting(body, operands: int, hp: int, depth: int):
    """The kernel of a one-token step over a whole state ``[L, B, H, ...]``
    that stays in HBM, whatever the step: the live slots' states come
    through VMEM in pieces of ``hp`` heads, slot by slot and within a slot
    piece by piece, through rings of ``depth`` pieces. The reads of the
    next ``depth - 1`` pieces are on their way in and the writes of the
    last ``depth`` on their way back while ``body(b, h0, sbuf, obuf, entry,
    *operands, y_ref)`` makes heads ``h0 .. h0 + hp - 1`` of slot ``b``'s
    new state, ``obuf[entry]``, and their part of ``y_ref[b]`` from
    ``sbuf[entry]`` and the ``operands`` small arrays in VMEM. A piece's
    place in its slot is static (a head's operands are cut by a constant);
    only the slot and the ring's entry are read at run time. A slot that is
    not live starts no DMA in either direction."""

    def kernel(layer_ref, live_ref, *refs):
        small = refs[:operands]
        s_hbm, y_ref, so_hbm, sbuf, obuf, read_sem, write_sem = refs[operands:]
        B = y_ref.shape[0]
        pieces = s_hbm.shape[2] // hp       # of one slot
        visits = live_ref[B]    # the live slots' indices, then their count
        layer = layer_ref[0]

        def piece(v, p):
            """Piece ``p`` of visit ``v``, ``p`` counted on through the
            visits after (or back through those before) -> (its visit, its
            first head, its ring entry)."""
            dv, at = divmod(p, pieces)
            return v + dv, at * hp, (v * pieces + p) % depth

        def read(v, p):
            v, h0, entry = piece(v, p)
            return pltpu.make_async_copy(
                s_hbm.at[layer, live_ref[v], h0:h0 + hp], sbuf.at[entry],
                read_sem.at[entry])

        def write(v, p):
            v, h0, entry = piece(v, p)
            return pltpu.make_async_copy(
                obuf.at[entry], so_hbm.at[layer, live_ref[v], h0:h0 + hp],
                write_sem.at[entry])

        def when(cond, then):   # ``cond`` a Python bool where it is static
            if cond is True:
                then()
            elif cond is not False:
                pl.when(cond)(then)

        def visit(v, _):
            b = live_ref[v]
            for p in range(pieces):
                ahead = p + depth - 1
                # the piece ``depth - 1`` on sets out, into the entry the
                # piece before this one was read from
                when(ahead < pieces or v + ahead // pieces < visits,
                     lambda: read(v, ahead).start())
                read(v, p).wait()
                # the entry this piece is written into: sent back ``depth``
                # pieces ago
                when(p >= depth or v * pieces + p >= depth,
                     lambda: write(v, p - depth).wait())
                body(b, p * hp, sbuf, obuf, piece(v, p)[2], *small, y_ref)
                write(v, p).start()
            return 0

        # what no visit writes, a slot that is not live, leaves as zeros
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)
        for p in range(depth - 1):      # every first read before any wait
            when(p // pieces < visits, lambda: read(0, p).start())
        jax.lax.fori_loop(0, visits, visit, 0)
        for back in range(depth, 0, -1):    # the last pieces' writes
            when(visits * pieces >= back,
                 lambda: write(visits, -back).wait())

    return kernel


def visit_live(body, name: str, states, layer, live, operands, row,
               interpret=False):
    """``body`` (``_visiting``) over layer ``layer`` of ``states``
    [L, B, H, ...] float32, in place -> (y [B, *row] float32, states: the
    operand's own buffer). ``live`` (``decode_attention.live_slots``'
    [B + 1]; None: every slot) names the slots it visits; ``operands`` are
    the step's small arrays, each whole in VMEM. ``name`` is the custom
    call's, which the benchmark's readers know it by. ``interpret``: True,
    or a ``pltpu.InterpretParams``, runs the kernel on the CPU."""
    L, B, H, *head = states.shape
    f32 = jnp.float32
    if states.dtype != f32:
        raise ValueError(f"the kernel steps a float32 state, not "
                         f"{states.dtype}: the XLA path's ({name}_xla)")
    head_bytes = 4 * math.prod(head)
    hp = heads_a_piece(H, head_bytes)
    rings = 2 * DEPTH * hp * head_bytes
    if rings > RING_BYTES:
        raise ValueError(
            f"a head's state of {' x '.join(map(str, head))} goes through "
            f"VMEM whole, {2 * DEPTH} at a time: more than {RING_BYTES} "
            "bytes in all is not")
    if live is None:        # slots 0 .. B - 1, and B of them
        live = jnp.arange(B + 1, dtype=jnp.int32)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _visiting(body, len(operands), hp, DEPTH),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[vmem] * len(operands) + [hbm],
            out_specs=[vmem, hbm],
            scratch_shapes=[
                pltpu.VMEM((DEPTH, hp, *head), f32),
                pltpu.VMEM((DEPTH, hp, *head), f32),
                pltpu.SemaphoreType.DMA((DEPTH,)),
                pltpu.SemaphoreType.DMA((DEPTH,)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, *row), f32),
                   jax.ShapeDtypeStruct(states.shape, f32)],
        # operands count from the scalar-prefetch arguments on
        input_output_aliases={2 + len(operands): 1},
        # the two rings, and the small operands, whose rows pad to whole
        # tiles
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=rings + (32 << 20)),
        name=name,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live.astype(jnp.int32),
      *operands, states)


def _step(b, h0, sbuf, obuf, entry, decay_ref, xdt_ref, b_ref, c_ref,
          y_ref):
    heard, said = b_ref[b], c_ref[b]                      # [1, N]
    heads = [(i, slice(h0 + i, h0 + i + 1)) for i in range(sbuf.shape[1])]
    # Two passes over the piece. A head's decay and its dt * x lie down the
    # sublanes, [P, 1], and are spread along the lanes; its rows are summed
    # across the lanes: two trips through the same unit, and in ONE pass a
    # head's sum waits for its spread and the next head's spread for that
    # sum (7.6 us a slot's 64 heads on the v5e, where the passes apart take
    # 2.1 + 1.0).
    for i, head in heads:
        obuf[entry, i] = (decay_ref[b, :, head] * sbuf[entry, i]
                          + xdt_ref[b, :, head] * heard)
    for i, head in heads:
        y_ref[b, :, head] = jnp.sum(obuf[entry, i] * said, axis=-1,
                                    keepdims=True)


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
    dt = dt.astype(f32)
    # a head's scalars down the sublanes of its [P, N] tile: [B, P, H]
    decay = jnp.broadcast_to(jnp.exp(dt * A)[:, None, :], (B, P, H))
    xdt = jnp.swapaxes(dt[..., None] * x.astype(f32), 1, 2)
    y, states = visit_live(
        _step, "ssm_update", states, layer, live,
        (decay, xdt, Bm.astype(f32)[:, None, :], Cm.astype(f32)[:, None, :]),
        (P, H), interpret)
    return jnp.swapaxes(y, 1, 2), states


class Recurrence(NamedTuple):
    """A kind of state layer's recurrence, as ``kv_cache.recur`` runs it
    between the convolution and the cache: ``mixed`` is what left the
    convolution, ``gates`` the family's per-step numbers, a pytree of
    float32 arrays [.., H] or [.., H, ..] (a decay a head, or a key channel:
    ``ops/kda.py``; zeros at a step that is no token, which must then leave
    the state as it is), ``layer`` the layer's weights; y comes back float32
    [.., H, P]."""
    scope: str          # its operations run under ``<scope>.conv|scan|update``
    # (layer, mixed [B, T, C], gates, state [B, H, ..], chunk) -> (y, state)
    scan: Callable
    # (layer, state, mixed [B, C], gates, live [B] bool | None) -> (y, state)
    step: Callable
    # (layer, states [L, B, H, ..], index, mixed [B, C], gates, live
    # [B + 1] | None, interpret) -> (y, states): the kernel, in place
    kernel: Callable


def _operands(layer, mixed, state_shape):
    """x [.., H, P], B and C [.., N] of what left the convolution (side by
    side, B and C shared by the heads), and the layer's A and D [H]."""
    H, _, N = state_shape
    inner = mixed.shape[-1] - 2 * N
    x, Bm, Cm = jnp.split(mixed, [inner, inner + N], axis=-1)
    return (x.reshape(*x.shape[:-1], H, -1), Bm, Cm,
            -jnp.exp(layer["A_log"].astype(jnp.float32)),
            layer["D"].astype(jnp.float32))


def _skip(y, D, x):
    """``y_t = S_t C_t + D x_t``."""
    return y + D[:, None] * x.astype(jnp.float32)


def _mamba2_scan(layer, mixed, dt, state, chunk):
    x, Bm, Cm, A, D = _operands(layer, mixed, state.shape[1:])
    y, state = ssm_scan(x, dt, A, Bm, Cm, state, chunk)
    return _skip(y, D, x), state


def _mamba2_step(layer, state, mixed, dt, live):
    x, Bm, Cm, A, D = _operands(layer, mixed, state.shape[1:])
    y, state = ssm_update_xla(state, x, dt, A, Bm, Cm, live)
    return _skip(y, D, x), state


def _mamba2_kernel(layer, states, index, mixed, dt, live, interpret):
    x, Bm, Cm, A, D = _operands(layer, mixed, states.shape[2:])
    y, states = ssm_update(states, index, x, dt, A, Bm, Cm, live=live,
                           interpret=interpret)
    return _skip(y, D, x), states


MAMBA2 = Recurrence("ssm", _mamba2_scan, _mamba2_step, _mamba2_kernel)
