"""Attention ops: XLA reference impl + pallas TPU flash-attention kernel.

The reference framework has no attention code of its own (it delegates to
vLLM/torch — SURVEY.md §2.3/§5); in a TPU-native stack the kernel layer is
ours. Design:

- ``attention_xla``: einsum softmax attention. XLA fuses this well on TPU and
  it is the autodiff path.
- ``flash_attention``: blockwise online-softmax pallas kernels (VMEM-resident
  q/k/v blocks, bf16 operands, f32 accumulators and statistics), forward AND
  backward (FlashAttention-2-style tiling): the forward saves per-row
  logsumexp; the backward is ONE kernel over key blocks that computes S, P
  and dP once a block pair (five products) and carries dq in VMEM across the
  key blocks; the [Tq, Tk] score matrix is never materialized. Both hold
  scores keys x queries, skip what lies wholly above the diagonal, and mask
  only the block the diagonal crosses or the padded keys sit in
  (``flash_block_counts`` says what a call computes). Blocks are chosen from
  the sequence length; swept on a v5e in PR 37 (see ``DEFAULT_BLOCK_Q``).
  With a ``window`` (causal only) a query sees itself and the ``window - 1``
  positions before it: a query block visits the key blocks from the
  window's edge to the diagonal and no others, and the edge is one more
  boundary that is masked on the blocks it crosses alone. Query heads that
  share a kv head read that head's k and v where they lie (a block index,
  no copy).
- ``attention``: dispatcher — pallas on TPU, XLA elsewhere; tests run the
  same kernel code on the CPU mesh through ``impl="flash_interpret"``.

Shapes are heads-major throughout, [batch, heads, seq, head_dim]: what a
kernel folds into its [batch x heads, seq, head_dim] is then a reshape, and
the projections on either side write and read that order themselves
(``heads_in`` / ``heads_out`` below). Whatever a call still moves (the
padding of a sequence that is no multiple of its block, the slice that takes
it off again) lies under ``jax.named_scope("attn.fold")``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    bias: Optional[jax.Array] = None,
    q_offset: int | jax.Array = 0,
    kv_offset: int | jax.Array = 0,
    window: Optional[int] = None,
) -> jax.Array:
    """Dense attention. q: [B, H, Tq, D]; k: [B, Hkv, Tk, D]; v:
    [B, Hkv, Tk, Dv] -> [B, H, Tq, Dv].
    ``window`` (causal only): a query sees itself and the ``window - 1``
    positions before it, a band mask.

    Supports grouped-query attention (H a multiple of Hkv) and absolute
    position offsets so callers holding only a chunk of the sequence (ring /
    blockwise) mask correctly.
    """
    B, H, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = D ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias
    if causal:
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0) + q_offset
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1) + kv_offset
        mask = q_pos >= k_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# --------------------------------------------------------------------- pallas

# The largest block a call takes when the caller names none (``_blocks``
# chooses from the sequence). Swept on a v5e in PR 37 with the kernels as they
# are below, causal, forward + backward kernel time a call, at the training
# cells' shapes and at D = 128:
#   [256, 1024, 64] (one chip):      1024: 1.92 ms   512: 2.32   256: 3.98
#   [100, 1024, 64] (a chip of four): 1024: 0.75     512: 0.89   256: 1.46
#   [64, 2048, 128]:                  1024: 1.78     512: 1.99   256: 3.61
#   [32, 4096, 128]:                  1024: 3.22     512: 3.57
# (the kernels before PR 37, two backward passes and masks on every block, at
# their 512: 3.95, 1.53, 3.02, 5.19). At T = 1024 one block is the whole
# head: every step is known when the kernel is traced and nothing loops.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
LANES = 128
# Queries a step takes at once (same sweep, [256, 1024, 64], kernel ms).
# Forward: 512 (0.58; 256: 0.75, 1024: 0.70, 128: 1.15): q is the score
# product's stationary operand, and 512 queries are four tiles of MXU
# weights, one for each MXU; the 128-wide strip that would leave out more of
# what lies above the diagonal keeps one MXU of four busy. Backward: 128 on
# the block the diagonal crosses (1.34; 256: 1.47, 512: 1.62: its five
# products hold other weights, and skipping pays), 512 below it.
_FWD_STRIP = 512
_BWD_STRIP = 128
_BWD_CHUNK = 512
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _pad_to(n: int, block: int) -> int:
    return block * ((n + block - 1) // block)


def _block(seq: int, block: Optional[int], largest: int) -> int:
    """The block a call runs with: the caller's, cut to the sequence; else
    ``largest``, a half or a quarter of it, cut to the sequence in whole
    lanes: the one that pads the sequence least, the larger on a tie."""
    if block is not None:
        return min(block, seq)
    lanes = _pad_to(seq, LANES)
    return min((min(largest // f, lanes) for f in (1, 2, 4)),
               key=lambda b: (_pad_to(seq, b), -b))


def _blocks(seq_q: int, seq_k: int, block_q: Optional[int],
            block_k: Optional[int]) -> tuple:
    return (_block(seq_q, block_q, DEFAULT_BLOCK_Q),
            _block(seq_k, block_k, DEFAULT_BLOCK_K))


def _strip(block: int, want: int) -> int:
    """Width of the steps a block is walked in: ``want`` where that divides
    the block, else the block whole."""
    return want if block % want == 0 else block


def _square(seq_q: int, seq_k: int, block_q: int, block_k: int,
            causal: bool, window: Optional[int] = None) -> bool:
    """Causal with equal blocks over equally padded sequences: the one block
    of a row the diagonal crosses is block ``i == j``, known when the kernel
    is traced, and is walked in strips of queries, each against the keys at
    or before its last query alone. A window shorter than the block would
    cut into that block too: it is then masked like any other."""
    return (causal and block_q == block_k
            and _pad_to(seq_q, block_q) == _pad_to(seq_k, block_k)
            and (window is None or window >= block_q))


def _window_of(window: Optional[int], causal: bool, seq_k: int):
    """The window a call runs with: None where it leaves no key out."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(f"a window ({window}) is causal and at least 1")
    return window if window < seq_k else None


def flash_block_counts(seq_q: int, seq_k: int, block_q: Optional[int] = None,
                       block_k: Optional[int] = None,
                       causal: bool = True,
                       window: Optional[int] = None) -> dict:
    """What the flash kernels do for one head, from the shapes alone: the
    block pairs they visit, how many of those take the masked path (the
    diagonal crosses them, or they hold the padded tail of the keys), and
    the score elements the forward and the backward compute (a block on the
    diagonal is walked in strips of queries, 512 wide forward and 128
    backward, and what lies above a strip's last query is left out). With a
    ``window`` the blocks wholly before it are not visited and the blocks
    its edge crosses are masked, whole."""
    block_q, block_k = _blocks(seq_q, seq_k, block_q, block_k)
    window = _window_of(window, causal, seq_k)
    n_q = _pad_to(seq_q, block_q) // block_q
    n_k = _pad_to(seq_k, block_k) // block_k
    square = _square(seq_q, seq_k, block_q, block_k, causal, window)
    visited = masked = on_diagonal = 0
    for i in range(n_q):
        for j in range(n_k):
            if causal and j * block_k > (i + 1) * block_q - 1:
                continue  # wholly above the diagonal
            if window is not None and (j + 1) * block_k <= i * block_q - window + 1:
                continue  # wholly before the first query's window
            visited += 1
            plain = ((j + 1) * block_k <= seq_k
                     and (not causal or (j + 1) * block_k - 1 <= i * block_q)
                     and (window is None
                          or j * block_k > (i + 1) * block_q - 1 - window))
            masked += not plain
            on_diagonal += square and i == j

    def elements(want: int) -> int:
        strips = block_q * block_k
        if square:  # the diagonal's block is walked in strips
            w = _strip(block_q, want)
            strips = sum(w * (c + w) for c in range(0, block_q, w))
        return ((visited - on_diagonal) * block_q * block_k
                + on_diagonal * strips)

    return {"visited": visited, "masked": masked,
            "elements_fwd": elements(_FWD_STRIP),
            "elements_bwd": elements(_BWD_STRIP)}


def _fold_scale(dtype, scale: float) -> bool:
    """May the softmax scale ride on a [block, D] operand instead of the
    f32 scores? Where it rounds nothing: a power of two (D = 64: 1/8), or
    f32 operands."""
    return dtype == jnp.float32 or math.log2(scale).is_integer()


def _keep(shape, *, lead, causal: bool, k_left, window=None):
    """Which scores of a [keys, queries] tile count: key before ``k_left``
    (None: all are, the tile holds no padded key) and, if causal, not after
    its query, nor ``window`` or more before it. ``lead``: the first query's
    position less the first key's."""
    k_i = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    keep = None
    if k_left is not None:
        keep = k_i < k_left
    if causal:
        q_i = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        under = (k_i - q_i) <= lead
        keep = under if keep is None else keep & under
        if window is not None:
            keep &= (k_i - q_i) > lead - window
    return keep


def _flash_kernel(q_ref, k_ref, v_ref, *rest, block_k: int,
                  strip: int, causal: bool, scale: float, fold: bool,
                  seq_k: int, square: bool, window: Optional[int] = None,
                  shared: bool = False):
    """One (batch*head, q_block) program: stream K/V blocks with online
    softmax. Block shapes: q [1, Bq, D], k [1, Tk, D], v [1, Tk, Dv], o
    [1, Bq, Dv] (Dv = D but for latent attention), lse [1, 8, Bq]
    (written only when the training path asks for it: it feeds the backward);
    scratch m/l [1, Bq], acc [Dv, Bq]. With a ``shared`` key (a fourth
    operand [1, Tk, Dr], one block a batch row that every head of it reads)
    k is [1, Tk, D - Dr] and a score is two products, q's first channels
    with k and its last Dr with the shared key. Scores are held keys x queries
    ([n, strip]): a query's max and sum reduce along sublanes and broadcast
    back along them, and q is the product's stationary operand.

    A block wholly under the diagonal and inside the true sequence takes the
    plain step: no iota, compare or select. Only the block the diagonal
    crosses, or the one with the padded keys, is masked. Where that block is
    known when the kernel is traced (``square``) each strip of its queries
    meets the keys up to the strip's last query in one step, masked on the
    strip's own keys alone; with one key block in all, that step is the
    whole softmax and nothing is rescaled. With a ``window`` the walk starts
    at the block that holds the first query's oldest visible key, and the
    blocks before the first one that every query of the block sees whole
    are masked too."""
    s_ref, rest = (rest[0], rest[1:]) if shared else (None, rest)
    o_ref, *lse_ref, m_ref, l_ref, acc_ref = rest
    Dn = k_ref.shape[2]
    qi = pl.program_id(1)
    block_q = q_ref.shape[1]
    q_off = qi * block_q
    # Operands stay in the input dtype (bf16): the MXU runs low-precision
    # multiplies with f32 accumulation (preferred_element_type) at ~2x the
    # f32xf32 rate. The softmax scale rides on q where that rounds nothing
    # (a power of two, or f32 operands), else on the f32 scores.
    q = q_ref[0]  # [Bq, D]
    if fold:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    tail = seq_k % block_k != 0
    only_block = square and k_ref.shape[1] == block_k

    def scores(k_off, n: int, c: int, lead):
        """Keys ``k_off:+n`` against queries ``c:+strip`` of the block;
        ``lead`` as in ``_keep``, None for the plain step."""
        s = jax.lax.dot_general(k_ref[0, pl.ds(k_off, n), :],
                                q[c:c + strip, :Dn],
                                _NT, preferred_element_type=jnp.float32)
        if shared:
            s += jax.lax.dot_general(
                s_ref[0, pl.ds(k_off, n), :], q[c:c + strip, Dn:], _NT,
                preferred_element_type=jnp.float32)
        if not fold:
            s = s * scale
        if lead is not None:
            keep = _keep(s.shape, lead=lead, causal=causal,
                         k_left=seq_k - k_off if tail else None,
                         window=window)
            s = jnp.where(keep, s, NEG_INF)
        return s

    def update(parts, c: int, first: bool):
        """Take ``parts`` [(scores, k_off, n)] into the softmax of queries
        ``c:+strip``; ``first``: nothing was taken before."""
        cols = slice(c, c + strip)
        m_new = functools.reduce(jnp.maximum, [
            jnp.max(s, axis=0, keepdims=True) for s, _, _ in parts])
        if not first:
            m_prev = m_ref[:, cols]
            m_new = jnp.maximum(m_prev, m_new)
        l_new = acc = 0.0
        for s, k_off, n in parts:
            p = jnp.exp(s - m_new)
            l_new = l_new + jnp.sum(p, axis=0, keepdims=True)
            v_c = v_ref[0, pl.ds(k_off, n), :]
            # p in [0, 1]: bf16 rounding is harmless and keeps PV on the fast
            # MXU path (the f32 accumulator preserves the sum's precision).
            acc = acc + jax.lax.dot_general(
                v_c, p.astype(v_c.dtype), _TN,
                preferred_element_type=jnp.float32)  # [D, strip]
        if not first:
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_ref[:, cols] + l_new
            acc = alpha * acc_ref[:, cols] + acc
        m_ref[:, cols] = m_new
        l_ref[:, cols] = l_new
        acc_ref[:, cols] = acc

    def block(masked: bool):
        def body(j, _):
            k_off = pl.multiple_of(j * block_k, block_k)
            for c in range(0, block_q, strip):
                lead = q_off + c - k_off if masked else None
                update([(scores(k_off, block_k, c, lead), k_off, block_k)],
                       c, False)
        return body

    if not only_block:
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        n_visit = k_ref.shape[1] // block_k
        n_plain = seq_k // block_k  # blocks with no padded key
        if causal:
            n_plain = jnp.minimum(n_plain, (q_off + 1) // block_k)
            n_visit = jnp.minimum(n_visit,
                                  (q_off + block_q - 1) // block_k + 1)
        first_plain = 0
        if window is not None:
            # the block of the first query's oldest visible key, and the
            # first block every query of this one sees whole
            first = jnp.maximum(q_off - window + 1, 0) // block_k
            first_plain = jnp.minimum(n_plain, jnp.maximum(
                q_off + block_q - window + block_k - 1, 0) // block_k)
            jax.lax.fori_loop(first, first_plain, block(True), None)
        jax.lax.fori_loop(first_plain, n_plain, block(False), None)
        if not square and (causal or tail):
            jax.lax.fori_loop(n_plain, n_visit, block(True), None)
    if square:
        k0 = pl.multiple_of(q_off, block_q)
        for c in range(0, block_q, strip):
            if c and not tail:
                parts = [(scores(k0, c, c, None), k0, c),
                         (scores(k0 + c, strip, c, 0), k0 + c, strip)]
            else:
                parts = [(scores(k0, c + strip, c, c), k0, c + strip)]
            update(parts, c, only_block)

    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / l).T.astype(o_ref.dtype)
    if lse_ref:
        # lse = m + log(l). Stored 8x-replicated on the sublane dim: mosaic
        # requires block shapes (8, 128)-divisible, so a [Bq]-vector per
        # program rides as an [8, Bq] tile (negligible bytes, legal layout).
        lse_ref[0][0] = jnp.broadcast_to(m_ref[...] + jnp.log(l),
                                         (8, block_q))


def _vmem_limit(resident_bytes: int):
    """Compiler parameters for a call that keeps ``resident_bytes`` of
    blocks and scratch in VMEM: the compiler's own limit (16 MiB) where that
    holds them twice over (every block is double-buffered), else room for
    them and the steps' temporaries, inside a v5e core's 128 MiB."""
    need = 2 * resident_bytes + (8 << 20)
    if need <= 16 << 20:
        return {}
    return {"vmem_limit_bytes": min(need, 100 << 20)}


def _folded(x, seq_p: int):
    """[B, H.., T, D] -> [B x H.., T padded to ``seq_p``, D]: a reshape of
    the array in row-major order, and a copy only where the sequence is no
    multiple of its block."""
    *lead, T, D = x.shape
    if seq_p != T:
        with jax.named_scope("attn.fold"):
            x = jnp.pad(x, ((0, 0),) * len(lead) + ((0, seq_p - T), (0, 0)))
    if D >= LANES:
        # said as a constraint, the kernels' order reaches the product that
        # makes x; left to the call's own operand layout it is met by a
        # copy. Not under 128 channels: such a row fills part of its lanes,
        # so an array kept in this order (a residual of every layer) is
        # padded to twice its size, and the compiler's own order, positions
        # minor, with a copy into the call is the cheaper (GPT-2's 64)
        x = with_layout_constraint(
            x, Layout(major_to_minor=tuple(range(x.ndim))))
    return x.reshape(math.prod(lead), seq_p, D)


def _unfolded(x, like, seq: int):
    """A call's [B x H, T padded, D] result as ``like``'s [B, H, ``seq``,
    D]."""
    x = x.reshape(*like.shape[:2], *x.shape[1:])
    if x.shape[2] != seq:
        with jax.named_scope("attn.fold"):
            x = x[:, :, :seq]
    return x


def _kv_index(rep: int):
    """Block index of the kv head that query head ``b`` of the folded
    [B*H] axis reads: its own, or the one its group of ``rep`` shares (the
    heads of a group are neighbours, so the block stays where it is from one
    program to the next and is fetched once a kv head)."""
    if rep == 1:
        return lambda b: b
    return lambda b: b // rep


def _call_name(direction: str, window, D: int, Dv: int) -> str:
    """The instruction's name, by which a trace's reader knows a call's
    kind: ``flash_fwd`` / ``flash_bwd``, ``flash_window_*`` with a window,
    ``flash_mla_*`` where the values are not as wide as the keys (latent
    attention's up-projected heads)."""
    kind = "mla_" if Dv != D else "" if window is None else "window_"
    return f"flash_{kind}{direction}"


def _flash_fwd_impl(q, k, v, shared=None, *, causal: bool,
                    block_q: Optional[int], block_k: Optional[int],
                    interpret: bool, with_lse: bool = False,
                    window: Optional[int] = None):
    B, H, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    Dv = v.shape[-1]    # the values' width, and the result's: D where equal
    rep = H // Hkv
    window = _window_of(window, causal, Tk)
    if window is not None and Tq != Tk:
        raise ValueError("a window is for self-attention: Tq == Tk")
    block_q, block_k = _blocks(Tq, Tk, block_q, block_k)
    # Pad sequences to block multiples: in-kernel dynamic slices on a
    # non-multiple tail would clamp and silently re-read earlier rows.
    # Pad keys are masked in-kernel via seq_k; pad q rows are sliced off.
    Tq_p, Tk_p = _pad_to(Tq, block_q), _pad_to(Tk, block_k)
    scale = D ** -0.5
    operands = [_folded(q, Tq_p), _folded(k, Tk_p), _folded(v, Tk_p)]
    kv = _kv_index(rep)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, Tk_p, k.shape[-1]), lambda b, i: (kv(b), 0, 0)),
        pl.BlockSpec((1, Tk_p, Dv), lambda b, i: (kv(b), 0, 0)),
    ]
    if shared is not None:
        # one block a batch row, the same for every head of it: fetched
        # once a row, as a grouped kv head is once a group
        operands.append(_folded(shared, Tk_p))
        in_specs.append(pl.BlockSpec((1, Tk_p, shared.shape[-1]),
                                     lambda b, i: (b // H, 0, 0)))
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, strip=_strip(block_q, _FWD_STRIP),
        causal=causal, scale=scale, fold=_fold_scale(q.dtype, scale),
        seq_k=Tk, square=_square(Tq, Tk, block_q, block_k, causal, window),
        window=window, shared=shared is not None,
    )
    out_shape = [jax.ShapeDtypeStruct((B * H, Tq_p, Dv), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, Dv), lambda b, i: (b, i, 0))]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((B * H, 8, Tq_p), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)))
    size = q.dtype.itemsize
    limit = _vmem_limit(Tk_p * (D + Dv) * size + block_q * (D + Dv) * size
                        + block_q * Dv * 4)
    # Without ``with_lse`` (inference, no grad) the LSE output does not
    # exist: it would be wasted write bandwidth on every forward.
    out, *lse = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(B * H, Tq_p // block_q),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),  # running max
            pltpu.VMEM((1, block_q), jnp.float32),  # running sum
            pltpu.VMEM((Dv, block_q), jnp.float32),  # o accumulator
        ],
        **({"compiler_params": pltpu.CompilerParams(**limit)}
           if limit else {}),
        interpret=interpret,
        name=_call_name("fwd", window, D, Dv),
    )(*operands)
    out = _unfolded(out, q, Tq)
    if with_lse:
        return out, lse[0]  # [B*H, 8, Tq_p], the backward's layout
    return out


def _flash_bwd_kernel(q_ref, k_ref, v_ref, *rest, block_q: int, strip: int,
                      chunk: int, causal: bool, scale: float, fold: bool,
                      seq_k: int, square: bool,
                      window: Optional[int] = None, heads: int = 0):
    """One (batch*head, k_block) program of the ONE backward pass: dK/dV of
    this key block and this key block's part of dQ, from S, P and dP computed
    once a block pair (five products). Scores are held keys x queries
    ([n, width]), so the saved row statistics broadcast along sublanes as
    they are stored and only dQ's product contracts over rows. Shapes:
    k/dk [1, Bk, D], v/dv [1, Bk, Dv]; q/dq [1, Tq, D], do [1, Tq, Dv];
    lse/delta [1, 8, Tq] (row 0 is
    the data; the 8 rows are sublane replication for mosaic's block-shape
    rules); scratch dq_acc [Tq, D] f32, alive across the key-block axis and
    written at its last step, dk_acc/dv_acc [Bk, D] f32. With a shared key
    (``heads``: how many heads read each of its blocks; a fourth operand
    [1, Bk, Dr] as the forward's) k and dk are D - Dr wide, dq's last Dr
    channels come from the shared key, and its gradient is a fourth result
    [1, Tk, Dr], one block a batch row that stays in VMEM while the row's
    heads add to its float32 scratch [Tk, Dr] and the last one writes it.

    Padded queries need no mask (their dO and delta are zero and their lse
    is finite); padded keys and the diagonal do, on the blocks that hold
    them alone. The block the diagonal crosses, where it is known when the
    kernel is traced (``square``), is walked in strips of queries, each
    against the keys at or before its last query. With a ``window`` the walk
    over the query blocks ends at the last one that sees a key of this
    block, and the blocks after the last one whose every query sees the
    block whole are masked too."""
    if heads:
        (s_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dsh_ref,
         dq_acc, dk_acc, dv_acc, dsh_acc) = rest
    else:
        (do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
         dq_acc, dk_acc, dv_acc) = rest
    kj = pl.program_id(1)
    n_kb = pl.num_programs(1)
    block_k, Dn = k_ref.shape[1:]
    k_off = kj * block_k
    tail = seq_k % block_k != 0

    def scaled(x):
        return (x.astype(jnp.float32) * scale).astype(x.dtype) if fold else x

    k = k_ref[0]  # [Bk, D]
    v = v_ref[0]
    ks = scaled(k)
    if heads:
        head = pl.program_id(0) % heads
        rows = pl.ds(pl.multiple_of(k_off, block_k), block_k)
        sh = s_ref[0]  # [Bk, Dr]
        shs = scaled(sh)

        @pl.when(head == 0)
        def _():
            dsh_acc[rows, :] = jnp.zeros((block_k, sh.shape[1]), jnp.float32)

    @pl.when(kj == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def step(q_off, width: int, n: int, lead):
        """Keys ``:n`` of the block against queries ``q_off:+width``;
        ``lead`` as in ``_keep``, None for the plain step."""
        cols = pl.ds(q_off, width)
        q_c = q_ref[0, cols, :]
        do_c = do_ref[0, cols, :]
        s = jax.lax.dot_general(ks[:n], q_c[:, :Dn], _NT,
                                preferred_element_type=jnp.float32)
        if heads:
            s += jax.lax.dot_general(shs[:n], q_c[:, Dn:], _NT,
                                     preferred_element_type=jnp.float32)
        if not fold:
            s = s * scale
        p = jnp.exp(s - lse_ref[0, 0:1, cols])
        if lead is not None:
            keep = _keep(s.shape, lead=lead, causal=causal,
                         k_left=seq_k - k_off if tail else None,
                         window=window)
            # exp(NEG_INF - lse) would underflow to 0 anyway; the select
            # after the exp gives bit-exact zeros.
            p = jnp.where(keep, p, 0.0)
        dv_acc[:n, :] += jnp.dot(p.astype(do_c.dtype), do_c,
                                 preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v[:n], do_c, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0:1, cols])).astype(q_c.dtype)
        dk_acc[:n, :] += jnp.dot(ds, q_c[:, :Dn],
                                 preferred_element_type=jnp.float32)
        dq_acc[cols, :Dn] += jax.lax.dot_general(
            ds, k[:n], _TN, preferred_element_type=jnp.float32)
        if heads:
            dsh_acc[pl.ds(pl.multiple_of(k_off, block_k), n), :] += jnp.dot(
                ds, q_c[:, Dn:], preferred_element_type=jnp.float32)
            dq_acc[cols, Dn:] += jax.lax.dot_general(
                ds, sh[:n], _TN, preferred_element_type=jnp.float32)

    def block(masked: bool):
        def body(i, _):
            for c in range(0, block_q, chunk):
                q_off = pl.multiple_of(i * block_q + c, chunk)
                step(q_off, chunk, block_k,
                     q_off - k_off if masked else None)
        return body

    n_q = q_ref.shape[1] // block_q
    end = plain_end = n_q
    if window is not None:
        # past the last query block that sees a key of this block, and past
        # the last one whose every query sees all of it
        end = jnp.minimum(n_q, (k_off + block_k + window - 2) // block_q + 1)
        plain_end = jnp.minimum(end, (k_off + window) // block_q)
    if square:
        for c in range(0, block_q, strip):
            step(pl.multiple_of(k_off + c, strip), strip, c + strip, c)
        jax.lax.fori_loop(kj + 1, plain_end, block(False), None)
        if window is not None:
            jax.lax.fori_loop(jnp.maximum(plain_end, kj + 1), end,
                              block(True), None)
    elif window is not None:
        start = k_off // block_q
        first_plain = (k_off + block_k + block_q - 2) // block_q
        if tail:
            first_plain = jnp.where(kj == n_kb - 1, n_q, first_plain)
        first_plain = jnp.minimum(first_plain, end)
        plain_end = jnp.maximum(plain_end, first_plain)
        jax.lax.fori_loop(start, first_plain, block(True), None)
        jax.lax.fori_loop(first_plain, plain_end, block(False), None)
        jax.lax.fori_loop(plain_end, end, block(True), None)
    else:
        start = first_plain = 0
        if causal:
            # Lowest Q block that can see this K block, and the lowest one
            # that sees all of it.
            start = k_off // block_q
            first_plain = (k_off + block_k + block_q - 2) // block_q
        if tail:
            first_plain = jnp.where(kj == n_kb - 1, n_q, first_plain)
        if causal or tail:
            first_plain = jnp.minimum(first_plain, n_q)
            jax.lax.fori_loop(start, first_plain, block(True), None)
        jax.lax.fori_loop(first_plain, n_q, block(False), None)

    # dS was left unscaled: the scale goes on the [*, D] results.
    dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
    if heads:
        @pl.when(head == heads - 1)
        def _():
            dsh_ref[0, rows, :] = (dsh_acc[rows, :] * scale).astype(
                dsh_ref.dtype)

    @pl.when(kj == n_kb - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_impl(q, k, v, shared, out, lse, g, *, causal: bool,
                    block_q: Optional[int], block_k: Optional[int],
                    interpret: bool, window: Optional[int] = None):
    """Pallas flash backward, one call: no [Tq, Tk] materialization. Returns
    (dq, dk, dv, the shared key's gradient or None) with GQA head-group
    reduction applied: every query head reads its kv head's k and v where
    they lie and writes a dk and dv of its own, which are summed over the
    group afterwards. A shared key's gradient is summed over its heads
    inside the call."""
    B, H, Tq, D = q.shape
    _, Hkv, Tk, Dn = k.shape    # Dn: D, less a shared key's width
    Dv = v.shape[-1]    # of v, out, g and dv; q and dq are D wide
    rep = H // Hkv
    window = _window_of(window, causal, Tk)
    block_q, block_k = _blocks(Tq, Tk, block_q, block_k)
    scale = D ** -0.5
    Tq_p, Tk_p = _pad_to(Tq, block_q), _pad_to(Tk, block_k)
    qf, dof, of = _folded(q, Tq_p), _folded(g, Tq_p), _folded(out, Tq_p)
    kf, vf = _folded(k, Tk_p), _folded(v, Tk_p)
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise reduce in XLA,
    # replicated to the same [B*H, 8, Tq] sublane layout as lse.
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (B * H, 8, Tq_p))

    kv = _kv_index(rep)
    size = q.dtype.itemsize
    limit = _vmem_limit(Tq_p * (2 * D + Dv) * size + Tq_p * D * 4
                        + 2 * 8 * Tq_p * 4
                        + 2 * block_k * (D + Dv) * (size + 2)
                        + (0 if shared is None else Tk_p * LANES * (4 + size)))

    def whole_q(width):
        return pl.BlockSpec((1, Tq_p, width), lambda b, j: (b, 0, 0))

    def k_block(width):
        return pl.BlockSpec((1, block_k, width), lambda b, j: (kv(b), j, 0))

    def dk_block(width):
        return pl.BlockSpec((1, block_k, width), lambda b, j: (b, j, 0))

    stats = pl.BlockSpec((1, 8, Tq_p), lambda b, j: (b, 0, 0))
    operands = [qf, kf, vf]
    in_specs = [whole_q(D), k_block(Dn), k_block(Dv)]
    out_shape = [jax.ShapeDtypeStruct((B * H, Tq_p, D), q.dtype),
                 jax.ShapeDtypeStruct((B * H, Tk_p, Dn), q.dtype),
                 jax.ShapeDtypeStruct((B * H, Tk_p, Dv), q.dtype)]
    out_specs = [whole_q(D), dk_block(Dn), dk_block(Dv)]
    scratch = [pltpu.VMEM((Tq_p, D), jnp.float32),      # dq, across key blocks
               pltpu.VMEM((block_k, Dn), jnp.float32),  # dk
               pltpu.VMEM((block_k, Dv), jnp.float32)]  # dv
    semantics = ("parallel", "arbitrary")
    if shared is not None:
        Dr = shared.shape[-1]
        operands.append(_folded(shared, Tk_p))
        in_specs.append(pl.BlockSpec((1, block_k, Dr),
                                     lambda b, j: (b // H, j, 0)))
        # a batch row's block is revisited by each of its heads in turn, so
        # the heads run one after another and none on another core
        out_shape.append(jax.ShapeDtypeStruct((B, Tk_p, Dr), q.dtype))
        out_specs.append(pl.BlockSpec((1, Tk_p, Dr),
                                      lambda b, j: (b // H, 0, 0)))
        scratch.append(pltpu.VMEM((Tk_p, Dr), jnp.float32))
        semantics = ("arbitrary", "arbitrary")
    dq, dk, dv, *dshared = pl.pallas_call(
        functools.partial(
            _flash_bwd_kernel, block_q=block_q,
            strip=_strip(block_q, _BWD_STRIP),
            chunk=_strip(block_q, _BWD_CHUNK), causal=causal, scale=scale,
            fold=_fold_scale(q.dtype, scale), seq_k=Tk,
            square=_square(Tq, Tk, block_q, block_k, causal, window),
            window=window, heads=0 if shared is None else H,
        ),
        out_shape=out_shape,
        grid=(B * H, Tk_p // block_k),
        in_specs=in_specs + [whole_q(Dv), stats, stats],
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, **limit),
        interpret=interpret,
        name=_call_name("bwd", window, D, Dv),
    )(*operands, dof, lse, delta)

    dq, dk, dv = _unfolded(dq, q, Tq), _unfolded(dk, q, Tk), \
        _unfolded(dv, q, Tk)
    if rep != 1:
        with jax.named_scope("attn.fold"):
            dk = dk.reshape(B, Hkv, rep, Tk, Dn).sum(axis=2)
            dv = dv.reshape(B, Hkv, rep, Tk, Dv).sum(axis=2)
    if shared is None:
        return dq, dk, dv, None
    with jax.named_scope("attn.fold"):
        return dq, dk, dv, dshared[0][:, :Tk]


def swapped(*arrays):
    """[B, T, H, D] <-> [B, H, T, D], each of ``arrays``: a copy of each,
    under the scope that says so in a trace. For code whose arrays are
    written in one order only, at its own door."""
    with jax.named_scope("attn.fold"):
        return tuple(a.swapaxes(1, 2) for a in arrays)


def flash_attention(q, k, v, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False,
                    window: Optional[int] = None, shared=None):
    """Flash attention: pallas forward AND pallas backward (LSE saved by
    the forward; backward never materializes the [Tq, Tk] score matrix —
    round 2 recomputed attention in XLA for grads, which put three dense
    [B, H, Tq, Tk] tensors back into every train step). q [B, H, T, D], k
    [B, Hkv, T, D] and v [B, Hkv, T, Dv] with H a multiple of Hkv ->
    [B, H, T, Dv]; the values may be narrower or wider than the keys (latent
    attention: 192 and 128), and where they are not the calls are the ones
    they were; ``window`` as ``attention_xla``'s. ``shared`` [B, T, Dr]: a
    key every head shares (latent attention's rotated one), read where it
    lies: k is then [B, H, T, D - Dr], each head's own channels, and the
    scores are those of ``concatenate([k, shared a head], -1)``.

    Heads under 128 channels wide (GPT-2's 64) cross the calls' boundary
    POSITIONS-major, [B, T, H, D], as their projection's product writes
    them: a row of the kernels' order fills part of its lanes, so nothing
    is kept in that order and the arrays are copied into and out of it
    around each call, inside the ``custom_vjp`` (``_flash_narrow``). A
    caller that transposed such arrays to get here (``gpt2.qkv``) has its
    transposes undone by the ones here, and the compiler drops both: every
    heads-major product tried at that width lost on one cell or another
    (my chip runs, PR 56: three products of the fused matrix's slices +2.9%
    on one chip and -3.2% on four, three rings of weight shards under
    ``fsdp`` where one was; the fused product transposed outside the
    ``custom_vjp`` -1.8 to -2.6% on one chip)."""
    if q.shape[-1] < LANES and shared is None:
        (out,) = swapped(_flash_narrow(
            *swapped(q, k, v), causal, block_q, block_k, interpret, window))
        return out
    return _flash_wide(q, k, v, causal, block_q, block_k, interpret, window,
                       shared)


def _named(out, lse):
    """The forward's residuals under the names a remat policy keeps them
    by: without them a jax.checkpoint around the transformer block re-runs
    the flash forward a second time in the backward pass just to rebuild
    them."""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(out, "flash_out"), checkpoint_name(lse, "flash_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_wide(q, k, v, causal, block_q, block_k, interpret, window, shared):
    return _flash_fwd_impl(
        q, k, v, shared, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window,
    )


def _flash_wide_fwd(q, k, v, causal, block_q, block_k, interpret, window,
                    shared):
    out, lse = _named(*_flash_fwd_impl(
        q, k, v, shared, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, with_lse=True, window=window,
    ))
    return out, (q, k, v, shared, out, lse)


def _flash_wide_bwd(causal, block_q, block_k, interpret, window, res, g):
    q, k, v, shared, out, lse = res
    return _flash_bwd_impl(
        q, k, v, shared, out, lse, g, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, window=window,
    )


_flash_wide.defvjp(_flash_wide_fwd, _flash_wide_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_narrow(q, k, v, causal, block_q, block_k, interpret, window):
    """``flash_attention`` on [B, T, H, D] arrays, in and out: the
    transposes into and out of the kernels' order inside the rule, forward
    and backward, so that what is kept between them (q, k, v, the result)
    lies as the products around it wrote and read it."""
    (out,) = swapped(_flash_fwd_impl(
        *swapped(q, k, v), causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window))
    return out


def _flash_narrow_fwd(q, k, v, causal, block_q, block_k, interpret, window):
    out, lse = _flash_fwd_impl(
        *swapped(q, k, v), causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, with_lse=True, window=window)
    out, lse = _named(*swapped(out), lse)
    return out, (q, k, v, out, lse)


def _flash_narrow_bwd(causal, block_q, block_k, interpret, window, res, g):
    q, k, v, out, lse = res
    q, k, v, out, g = swapped(q, k, v, out, g)
    return swapped(*_flash_bwd_impl(
        q, k, v, None, out, lse, g, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, window=window)[:3])


_flash_narrow.defvjp(_flash_narrow_fwd, _flash_narrow_bwd)


def attention(
    q, k, v, *, causal: bool = True, impl: str = "auto",
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    mesh=None, window: Optional[int] = None, shared=None,
):
    """Dispatcher. impl: auto | xla | flash | flash_interpret. ``auto`` is
    decided by platform alone: the pallas kernel on TPU (a kernel that fails
    to lower or compile there raises — it never gives way to XLA), XLA
    attention elsewhere. q [B, H, T, D], k and v [B, Hkv, T, ..]; ``shared``
    as ``flash_attention``'s (XLA attends the key it stands for).

    ``mesh``: the mesh the surrounding jit is sharded over. GSPMD cannot
    partition a Mosaic custom call, so on a mesh of several devices the
    kernel runs under ``shard_map``, each device on its shard of batch
    (``data``/``fsdp``) and heads (``tensor``), the full sequence local."""
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        if shared is not None:
            k = with_shared(k, shared)
        return attention_xla(q, k, v, causal=causal, window=window)
    if impl not in ("flash", "flash_interpret"):
        raise ValueError(f"unknown attention impl {impl}")

    def kernel(q, k, v, *shared):
        return flash_attention(
            q, k, v, causal, block_q, block_k, impl == "flash_interpret",
            window, *shared,
        )

    shared = () if shared is None else (shared,)
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        # Inside someone else's shard_map (pipeline_apply is manual over
        # ``stage``) the mesh comes from the context and only the axes that
        # are still automatic can be taken; taking all of them leaves no axis
        # for GSPMD to partition the call over.
        ctx = jax.sharding.get_abstract_mesh()
        held = set(ctx.manual_axes)
        free = set(mesh.axis_names) - held
        batch = tuple(a for a in ("data", "fsdp") if a in free)
        heads = "tensor" if "tensor" in free else None
        spec = P(batch or None, heads, None, None)
        if heads and k.shape[1] % mesh.shape["tensor"]:
            # fewer kv heads than the axis divides: every shard needs whole
            # groups, so the kv heads are spread to the query heads first
            rep = q.shape[1] // k.shape[1]
            with jax.named_scope("attn.fold"):
                k, v = (jnp.repeat(a, rep, axis=1) for a in (k, v))
        if free:
            kernel = jax.shard_map(
                kernel, mesh=None if held else mesh, axis_names=free,
                in_specs=(spec,) * 3
                + (P(batch or None, None, None),) * len(shared),
                out_specs=spec, check_vma=False,
            )
    return kernel(q, k, v, *shared)


def with_shared(k, shared):
    """k [B, H, T, Dn] with ``shared`` [B, T, Dr] behind each head's
    channels: the keys [B, H, T, Dn + Dr] that a call with a shared key
    attends without making them (XLA's path and the tests' oracles do)."""
    return jnp.concatenate([k, jnp.broadcast_to(
        shared[:, None], k.shape[:3] + shared.shape[-1:])], axis=-1)


# ------------------------------------------- the projections on either side
# What stands between a projection's product and a kernel is a reshape only if
# the product writes [B, H, T, D] with D minor, and between a kernel and the
# weights' gradient only if that product reads it so. The chip's compiler lays
# a product's result out by its own rule (positions minor wherever a width is
# under the 128 lanes) and meets a Mosaic call's operand layout with a copy of
# the whole array. Two things make it write and read the kernels' order
# instead (sandbox compiles for a described v5e, PR 56;
# ``tests/test_tpu_compile.py`` counts the copies that are left): ``_folded``
# states the order as a layout constraint, which the compiler carries back
# through a rotation and a concatenation to the product itself; and the
# heads-major projection names its WEIGHTS first, so that its transpose, the
# weights' gradient, takes dq, dk and dv as they lie (with the activations
# first it asks for them positions-minor: three copies a layer).


def heads_in(x, w, heads_major: bool):
    """x [B, T, E] through w [E, H, D] -> [B, H, T, D] where ``heads_major``
    (the full forward: what a kernel folds by a reshape), else [B, T, H, D]
    (the cached forward's rows)."""
    if heads_major:
        return jnp.einsum("ehd,bte->bhtd", w, x)
    return jnp.einsum("bte,ehd->bthd", x, w)


def heads_out(attn, w, heads_major: bool):
    """The heads, [B, H, T, D] where ``heads_major`` else [B, T, H, D],
    through w [H, D, E] -> [B, T, E]."""
    return jnp.einsum("bhtd,hde->bte" if heads_major else "bthd,hde->bte",
                      attn, w)
