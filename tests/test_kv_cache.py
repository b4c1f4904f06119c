"""The KV cache contract (``models/kv_cache.py``) through the engine's own
programs: cached decoding equals the full forward, for every way the engine
calls ``forward_cached``, and a tick changes only the positions it writes.

float32 throughout, so "equals" is 1e-4 and a write that lost precision
would show; the untouched rows are compared bit for bit.

On the CPU a decode step takes the XLA path; the last tests hold the TPU's
kernels (``ops/decode_attention.py`` for a decode step,
``ops/block_attention.py`` for a block of tokens, ``interpret=True``) to it,
alone and through ``forward_cached``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import engine_programs
from ray_tpu.models import kv_cache, module_for
from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import block_attention as block_kernel
from ray_tpu.ops import decode_attention as kernel

S, SLOTS, BUCKET, VOCAB = 32, 3, 8, 128

CONFIGS = {
    "gpt2": GPT2Config(
        vocab_size=VOCAB, max_seq_len=S, num_layers=2, num_heads=2,
        embed_dim=32, dtype=jnp.float32, remat=False,
    ),
    # grouped queries: 4 heads share 2 kv heads
    "llama_gqa": LlamaConfig(
        vocab_size=VOCAB, max_seq_len=S, num_layers=2, num_heads=4,
        num_kv_heads=2, embed_dim=64, dtype=jnp.float32, remat=False,
    ),
    # num_kv_heads == num_heads must behave as plain MHA (g = 1)
    "llama_mha": LlamaConfig(
        vocab_size=VOCAB, max_seq_len=S, num_layers=1, num_heads=4,
        num_kv_heads=4, embed_dim=32, dtype=jnp.float32, remat=False,
    ),
}
# slot -> (prompt length, tokens in all): three slots at three lengths,
# the first prompt in a non-zero slot
SEQS = {2: (5, 14), 0: (3, 12), 1: (7, 16)}


class _Model:
    def __init__(self, family):
        self.cfg = CONFIGS[family]
        self.mod = module_for(self.cfg)
        self.params = self.mod.init_params(self.cfg, jax.random.PRNGKey(1))
        (self.prefill, self.insert, self.decode,
         self.decode_all) = engine_programs(self.cfg)
        rng = np.random.RandomState(1)
        self.tokens = {
            b: rng.randint(0, VOCAB, n).astype(np.int32)
            for b, (_, n) in SEQS.items()
        }
        self.full = {
            b: np.asarray(self.mod.forward(
                self.params, jnp.asarray(t[None]), self.cfg)[0][0])
            for b, t in self.tokens.items()
        }

    def prefill_slot(self, b, upto, cache1=None, start=0):
        """Tokens [start, upto) of slot b's sequence, padded to the bucket,
        into ``cache1`` (or an empty slot cache); checks the logits."""
        toks = np.zeros((1, BUCKET), np.int32)
        toks[0, : upto - start] = self.tokens[b][start:upto]
        if cache1 is None:
            cache1 = self.mod.init_kv_cache(self.cfg, 1, S)
        logits, cache1 = self.prefill(
            self.params, jnp.asarray(toks), cache1,
            jnp.full((1,), start, jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(logits)[0, : upto - start], self.full[b][start:upto],
            rtol=1e-4, atol=1e-4,
        )
        return cache1

    def batch_after_prefill(self):
        """Every slot prefilled with its prompt and inserted."""
        cache = self.mod.init_kv_cache(self.cfg, SLOTS, S)
        for b, (plen, _) in SEQS.items():
            cache = self.insert(cache, self.prefill_slot(b, plen), b)
        return cache, np.array(
            [SEQS[b][0] for b in range(SLOTS)], np.int32)

    def step_tokens(self, lens, width):
        return jnp.asarray(np.stack([
            self.tokens[b][lens[b]: lens[b] + width] for b in range(SLOTS)
        ]))

    def tick(self, toks, cache, lens, before=None):
        """``decode`` as the engine calls it: the host's ``[B, 1]`` tokens
        the lengths and ``real`` (every slot decodes) in one packed array,
        beside the ids of the tick before (zeros: every token here is the
        host's) -> (logits, cache),
        the program's ids held to its logits."""
        if before is None:
            before = jnp.zeros((SLOTS,), jnp.int32)
        ids, logits, cache = self.decode(
            self.params, before, cache,
            jnp.stack([jnp.asarray(toks)[:, 0], jnp.asarray(lens),
                       jnp.ones((SLOTS,), jnp.int32)]))
        assert ids.dtype == jnp.int32
        np.testing.assert_array_equal(
            np.asarray(ids), np.asarray(logits).argmax(-1))
        return logits, cache


@pytest.fixture(scope="module", params=list(CONFIGS))
def model(request):
    return _Model(request.param)


def test_prefill_insert_then_ticks_at_different_lengths(model):
    cache, lens = model.batch_after_prefill()
    for _ in range(5):
        logits, cache = model.tick(model.step_tokens(lens, 1), cache, lens)
        for b in range(SLOTS):
            np.testing.assert_allclose(
                np.asarray(logits)[b], model.full[b][lens[b]],
                rtol=1e-4, atol=1e-4,
            )
        lens = lens + 1


def test_a_token_below_zero_is_the_tick_befores_own(model):
    """The engine one tick ahead: where the host gives no token (-1), a
    slot decodes the id the tick before chose for it, which never left the
    chip; where it gives one, that one, whatever ``before`` holds."""
    cache, lens = model.batch_after_prefill()
    toks = np.asarray(model.step_tokens(lens, 1))
    want, _ = model.tick(toks, jax.tree.map(jnp.copy, cache), lens)
    mixed = toks.copy()
    mixed[[0, 2], 0] = -1
    before = toks[:, 0].copy()
    before[1] = (before[1] + 7) % VOCAB  # slot 1's token is the host's
    got, _ = model.tick(mixed, cache, lens, before=jnp.asarray(before))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prefix_continuation(model):
    """start > 0 at B = 1: the prompt's tail on top of a cached prefix, whose
    own padded prefill left garbage behind position 4."""
    b = 1
    cache1 = model.prefill_slot(b, 4)
    cache1 = model.prefill_slot(b, 11, cache1, start=4)
    # and a tick on top of both, in the batch cache's last slot
    cache = model.insert(model.mod.init_kv_cache(model.cfg, SLOTS, S),
                         cache1, SLOTS - 1)
    toks = np.zeros((SLOTS, 1), np.int32)
    toks[-1, 0] = model.tokens[b][11]
    lens = np.array([0] * (SLOTS - 1) + [11], np.int32)
    logits, _ = model.tick(toks, cache, lens)
    np.testing.assert_allclose(
        np.asarray(logits)[-1], model.full[b][11], rtol=1e-4, atol=1e-4)


def test_decode_all_verifies_a_draft(model):
    """T = 1 + K: every position's logits, each slot from its own length."""
    cache, lens = model.batch_after_prefill()
    K = 2
    logits, cache = model.decode_all(
        model.params, model.step_tokens(lens, 1 + K), cache,
        jnp.asarray(lens))
    for b in range(SLOTS):
        np.testing.assert_allclose(
            np.asarray(logits)[b], model.full[b][lens[b]: lens[b] + 1 + K],
            rtol=1e-4, atol=1e-4,
        )
    # a rejected draft's positions are written over by the next tick
    lens = lens + 1
    logits, _ = model.tick(model.step_tokens(lens, 1), cache, lens)
    for b in range(SLOTS):
        np.testing.assert_allclose(
            np.asarray(logits)[b], model.full[b][lens[b]],
            rtol=1e-4, atol=1e-4,
        )


@pytest.mark.parametrize("width", [1, 3])
def test_a_tick_changes_only_the_positions_it_writes(model, width):
    cache, lens = model.batch_after_prefill()
    before = {k: np.asarray(v).copy() for k, v in cache.items()}
    if width == 1:
        _, cache = model.tick(model.step_tokens(lens, 1), cache, lens)
    else:
        _, cache = model.decode_all(
            model.params, model.step_tokens(lens, width), cache,
            jnp.asarray(lens))
    written = np.zeros((SLOTS, S), bool)
    for b in range(SLOTS):
        written[b, lens[b]: lens[b] + width] = True
    for name, old in before.items():
        new = np.asarray(cache[name])           # [L, B, KV, D, S]
        same = (new == old).all(axis=(0, 2, 3))  # [B, S]
        assert same[~written].all(), name
        assert not same[written].any(), name


def test_a_write_past_the_end_is_dropped(model):
    """The last position is written, what would lie behind it goes nowhere:
    no earlier row moves (a ``dynamic_update_slice`` would shift the whole
    write back over rows that hold valid K/V)."""
    cache, _ = model.batch_after_prefill()
    before = {k: np.asarray(v).copy() for k, v in cache.items()}
    lens = np.full((SLOTS,), S - 1, np.int32)
    _, cache = model.decode_all(
        model.params, jnp.ones((SLOTS, 3), jnp.int32), cache,
        jnp.asarray(lens))
    for name, old in before.items():
        new = np.asarray(cache[name])
        assert (new[..., : S - 1] == old[..., : S - 1]).all(), name
        assert (new[..., S - 1] != old[..., S - 1]).any(), name


# ------------------------------------------------- the decode step's kernel


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 128 positions at test sizes (the cells' are 512 of 1,024
    and of 4,096), so that a slot runs some blocks and skips the rest; and
    the kernel, interpreted, where the platform would choose XLA."""
    monkeypatch.setattr(kernel, "BLOCK_BYTES", 2 * 64 * 128 * 4)
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas_interpret")
    # the options of each kernel call that was traced (``live``: given?)
    traced = []
    monkeypatch.setattr(
        kv_cache, "decode_attention",
        lambda *a, **kw: traced.append(dict(kw, live=kw["live"] is not None))
        or kernel.decode_attention(*a, **kw))
    return traced


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("D, S", [(64, 384), (128, 512)])
def test_decode_kernel_equals_the_xla_path(small_blocks, monkeypatch, D, S,
                                           G, dtype):
    """One call, slots at different lengths: idle (0), mid-tile, a tile's
    last position, a later block, S - 1, and S (dropped, nothing written).
    Same attention within the dtype's rounding, the cache EQUAL bit for
    bit, other layers included."""
    L, KV = 2, 2
    lens = np.array([0, 70, 127, 300, S - 1, S], np.int32)
    B = len(lens)
    assert kernel._blocks(KV, D, S, jnp.dtype(dtype).itemsize)[1] == 128
    ks = jax.random.split(jax.random.PRNGKey(D + G), 5)
    cache = {
        "k": jax.random.normal(ks[0], (L, B, KV, D, S), dtype),
        "v": jax.random.normal(ks[1], (L, B, KV, D, S), dtype),
    }
    q = jax.random.normal(ks[2], (B, 1, KV, G, D), dtype)
    k_new = jax.random.normal(ks[3], (B, 1, KV, D), dtype)
    v_new = jax.random.normal(ks[4], (B, 1, KV, D), dtype)

    def attend():
        # a function of its own each time: jit's trace cache is by function
        return jax.jit(lambda cache: kv_cache.attend(
            cache, jnp.int32(1), q, k_new, v_new,
            kv_cache.step(jnp.asarray(lens), 1, cache)))(cache)

    got_cache, got = attend()
    assert small_blocks == [
        {"interpret": True, "window": None, "live": False}]
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "xla")
    want_cache, want = attend()
    assert len(small_blocks) == 1
    assert got.shape == want.shape and got.dtype == want.dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    for name in ("k", "v"):
        assert (np.asarray(got_cache[name]) == np.asarray(want_cache[name])
                ).all(), name
        # and what the XLA path wrote is what was asked for
        new = np.asarray(got_cache[name], np.float32)
        old = np.asarray(cache[name], np.float32)
        changed = (new != old).any(axis=(2, 3))              # [L, B, S]
        assert not changed[0].any()
        for b, n in enumerate(lens):
            assert list(np.flatnonzero(changed[1, b])) == ([n] if n < S else [])


LIVE = {"all": [1, 1, 1, 1, 1], "one": [0, 0, 1, 0, 0],
        "alternate": [1, 0, 1, 0, 1], "last": [0, 0, 0, 0, 1],
        "none": [0, 0, 0, 0, 0], "unsaid": None}


@pytest.mark.parametrize("pattern", list(LIVE))
@pytest.mark.parametrize("G", [1, 8])
@pytest.mark.parametrize("window", [None, 200], ids=["full", "ring"])
def test_decode_kernel_visits_the_live_slots_alone(small_blocks, window, G,
                                                   pattern):
    """The slots the call names decode as they do where every slot does,
    bit for bit: their rows of the result, their K and V. Any other slot,
    whatever its length says, keeps its K and V byte for byte and gets
    zeros; no slot named is no write at all, none said is all named. Two
    groups of kv heads a slot, several chunks a visit, lengths of 0 (a
    live slot may be empty), past a chunk's edge and round the ring."""
    L, KV, D, S = 2, 4, 64, 384
    lens = jnp.array([0, 70, 300, 129, 500 if window else S - 1], jnp.int32)
    B = len(lens)
    assert kernel._blocks(KV, D, S, 4) == (2, 128)
    ks = jax.random.split(jax.random.PRNGKey(G), 5)
    names = kv_cache.WINDOW if window else kv_cache.FULL
    cache = {name: jax.random.normal(key, (L, B, KV, D, S), jnp.float32)
             for name, key in zip(names, ks)}
    q = jax.random.normal(ks[2], (B, 1, KV, G, D), jnp.float32)
    k_new = jax.random.normal(ks[3], (B, 1, KV, D), jnp.float32)
    v_new = jax.random.normal(ks[4], (B, 1, KV, D), jnp.float32)

    def attend(live):
        if live is not None:
            live = jnp.asarray(live, bool)
        got_cache, got = jax.jit(lambda cache: kv_cache.attend(
            cache, jnp.int32(1), q, k_new, v_new,
            kv_cache.step(lens, 1, cache, window, live=live),
            windowed=window is not None))(cache)
        return jax.tree.map(np.asarray, (got_cache, got))

    want_cache, want = attend(LIVE["all"])
    got_cache, got = attend(LIVE[pattern])
    assert [call["window"] for call in small_blocks] == [window, window]
    live = np.array(LIVE[pattern] or LIVE["all"], bool)
    assert (got[live] == want[live]).all() and not got[~live].any()
    assert want.all()                     # a row computed is no row of zeros
    for name in names:
        new, old = got_cache[name], np.asarray(cache[name])
        assert (new[:, live] == want_cache[name][:, live]).all(), name
        assert (new[:, ~live] == old[:, ~live]).all(), name
        assert (new[0] == old[0]).all(), name
        # and a live slot's one column was written, on the layer asked
        assert ((new[1] != old[1]).any(axis=(1, 2)).sum(-1) == live).all()


def test_a_cache_the_lanes_do_not_divide_keeps_the_xla_path(small_blocks):
    """S = 96: the kernel's DMAs move whole tiles of 128 positions, and the
    chip's compiler refuses a slice of such a cache; the static shape
    decides, before anything is lowered."""
    B, KV, D, S = 2, 2, 64, 96
    cache = kv_cache.init_kv_cache(1, B, KV, D, S, jnp.float32)
    new = jnp.ones((B, 1, KV, D), jnp.float32)
    cache, out = kv_cache.attend(
        cache, jnp.int32(0), new, new, new,
        kv_cache.step(jnp.array([0, 95], jnp.int32), 1, cache))
    assert small_blocks == [] and out.shape == new.shape
    assert np.asarray(cache["k"])[0, :, 0, 0, [0, 95]].tolist() == [
        [1.0, 0.0], [0.0, 1.0]]
    with pytest.raises(ValueError, match="whole tiles"):
        kernel.decode_attention(new[:, 0, :, None], new[:, 0], new[:, 0],
                                cache["k"], cache["v"], 0, jnp.zeros(B))


# ------------------------------------------------ a block of tokens' kernel

# where a block of 64 tokens starts in a cache (or ring) of 512 positions
STARTS = {"empty": 0, "a_whole_chunk_in": 64, "an_unaligned_prefix": 37,
          "across_the_end": 480, "past_the_end": 1000}


@pytest.mark.parametrize("start", list(STARTS.values()), ids=list(STARTS))
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("G", [1, 8])
@pytest.mark.parametrize("window", [None, 300], ids=["full", "ring"])
def test_block_kernel_equals_the_xla_path(monkeypatch, window, G, D, start):
    """A prefill chunk of 64 tokens (its last 5 a bucket's padding, which
    attention takes as tokens on either path) against a cache of 512
    positions that holds other columns everywhere: the kernel's tiles are
    16 or 32 tokens and its blocks 128 positions, so a tile visits some
    blocks and skips the rest. The same attention within bfloat16's
    rounding, the cache EQUAL bit for bit, and changed only on the block's
    own positions of the layer asked: in a full layer the tokens past the
    end are dropped (a block across the end keeps its first 32, one past
    it none), in a ring they land round its end. No mask of [T, S] is built
    for the kernel."""
    L, KV, T, S, dtype = 2, 2, 64, 512, jnp.bfloat16
    monkeypatch.setattr(block_kernel, "BLOCK_BYTES", D * 128 * 2)
    monkeypatch.setattr(block_kernel, "ROWS", 32)
    assert block_kernel.tiles(T, G, D, S, 2) == (32 if G == 1 else 16, 128)
    traced = []
    monkeypatch.setattr(
        kv_cache, "block_attention",
        lambda *a, **kw: traced.append(kw)
        or block_kernel.block_attention(*a, **kw))
    names = kv_cache.WINDOW if window else kv_cache.FULL
    ks = jax.random.split(jax.random.PRNGKey(D + G + start), 5)
    cache = {name: jax.random.normal(key, (L, 1, KV, D, S), dtype)
             for name, key in zip(names, ks)}
    q = jax.random.normal(
        ks[2], (1, T, KV, G, D) if G > 1 else (1, T, KV, D), dtype)
    k_new = jax.random.normal(ks[3], (1, T, KV, D), dtype)
    v_new = jax.random.normal(ks[4], (1, T, KV, D), dtype)

    def attend(impl):
        monkeypatch.setattr(kv_cache, "_decode_impl", lambda: impl)
        at = kv_cache.step(jnp.array([start], jnp.int32), T, cache, window,
                           real=jnp.array([T - 5], jnp.int32))
        masks = (at.mask, at.hit, at.ring_mask, at.ring_hit)
        assert all(m is None for m in masks) == (impl != "xla")
        return jax.jit(lambda cache: kv_cache.attend(
            cache, jnp.int32(1), q, k_new, v_new, at,
            windowed=window is not None))(cache)

    got_cache, got = attend("pallas_interpret")
    assert traced == [{"window": window, "interpret": True}]
    want_cache, want = attend("xla")
    assert len(traced) == 1
    assert got.shape == want.shape == q.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
    positions = np.arange(start, start + T)
    landed = positions % S if window else positions[positions < S]
    for name, new in zip(names, (k_new, v_new)):
        assert (np.asarray(got_cache[name]) == np.asarray(want_cache[name])
                ).all(), name
        now = np.asarray(got_cache[name], np.float32)
        old = np.asarray(cache[name], np.float32)
        assert (now[0] == old[0]).all(), name
        untouched = np.setdiff1d(np.arange(S), landed)
        assert (now[1][..., untouched] == old[1][..., untouched]).all(), name
        # [T, KV, D] -> [KV, D, the tokens that landed]
        cols = np.asarray(new[0], np.float32).transpose(1, 2, 0)
        assert (now[1, 0][..., landed] == cols[..., :len(landed)]).all(), name


def test_a_block_the_kernel_does_not_take_keeps_the_xla_path(monkeypatch):
    """By static shapes alone, before anything is lowered: a few tokens at
    every slot (speculation's verify), a block that is no whole tile of
    tokens, a ring too short to hold the block beside the window; and the
    kernel itself refuses what ``attend`` would not hand it."""
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas_interpret")
    assert kv_cache._impl(1, 64, 512) == "pallas_interpret"
    assert kv_cache._impl(1, 64, 512, 200) == "pallas_interpret"
    assert kv_cache._impl(3, 64, 512) == "xla"      # several slots' blocks
    assert kv_cache._impl(1, 4, 512) == "xla"       # 1 + k tokens
    assert kv_cache._impl(1, 64, 96) == "xla"       # no whole lane tiles
    assert kv_cache._impl(1, 64, 256, 200) == "xla"  # 200 + 64 > 256
    assert kv_cache._impl(3, 1, 512) == "pallas_interpret"  # a decode step
    cache = jnp.zeros((1, 1, 2, 64, 256), jnp.float32)
    q = jnp.zeros((1, 64, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="holds no window"):
        block_kernel.block_attention(q, cache, cache, 0, jnp.zeros(1),
                                     window=200)
    with pytest.raises(ValueError, match="XLA path"):
        block_kernel.block_attention(q[:, :4], cache, cache, 0, jnp.zeros(1))


def test_positions_seen_by_a_block():
    """What ``engine.admit``'s ``prefill_key_positions`` sums a layer."""
    seen = kv_cache.positions_seen
    assert seen(0, 1024, 8704) == 1024 and seen(2048, 1024, 8704) == 3072
    assert seen(8192, 1024, 8704) == 8704            # no more than it holds
    # a window of 2048: the 2047 before the first token and the block
    assert seen(0, 2048, 4096, 2048) == 2048
    assert seen(100, 128, 4096, 2048) == 228
    assert seen(6144, 1024, 4096, 2048) == 2047 + 1024


BIG = {
    "gpt2": GPT2Config(
        vocab_size=VOCAB, max_seq_len=256, num_layers=2, num_heads=2,
        embed_dim=128, dtype=jnp.float32, remat=False,
    ),
    "llama_gqa": LlamaConfig(
        vocab_size=VOCAB, max_seq_len=256, num_layers=2, num_heads=4,
        num_kv_heads=2, embed_dim=256, dtype=jnp.float32, remat=False,
    ),
}


@pytest.mark.parametrize("family", list(BIG))
def test_prefill_then_kernel_steps_equal_the_full_forward(small_blocks,
                                                          family):
    """A prompt prefilled (T > 1, the XLA path) into one slot of three,
    then 8 decode steps through the kernel, across a tile's and a block's
    edge (positions 124-131), a short slot and an idle one beside it."""
    cfg = BIG[family]
    mod = module_for(cfg)
    params = mod.init_params(cfg, jax.random.PRNGKey(2))
    prefill, insert, decode, _ = engine_programs(cfg)
    rng = np.random.RandomState(2)
    seqs = {0: (124, rng.randint(0, VOCAB, 132).astype(np.int32)),
            2: (5, rng.randint(0, VOCAB, 13).astype(np.int32))}
    full = {b: np.asarray(mod.forward(params, jnp.asarray(t[None]), cfg)[0][0])
            for b, (_, t) in seqs.items()}
    cache = mod.init_kv_cache(cfg, 3, 256)
    lens = np.zeros((3,), np.int32)
    for b, (plen, toks) in seqs.items():
        padded = np.zeros((1, 128), np.int32)
        padded[0, :plen] = toks[:plen]
        _, cache1 = prefill(params, jnp.asarray(padded),
                            mod.init_kv_cache(cfg, 1, 256),
                            jnp.zeros((1,), jnp.int32))
        cache = insert(cache, cache1, b)
        lens[b] = plen
    for _ in range(8):
        toks = np.zeros((3, 1), np.int32)
        for b, (_, t) in seqs.items():
            toks[b, 0] = t[lens[b]]
        _, logits, cache = decode(
            params, jnp.zeros((3,), jnp.int32), cache,
            jnp.asarray(np.stack([toks[:, 0], lens, lens > 0])))
        for b in seqs:
            np.testing.assert_allclose(
                np.asarray(logits)[b], full[b][lens[b]],
                rtol=2e-4, atol=2e-4)
        lens[list(seqs)] += 1
    # one trace, in the scan, told which slots decode
    assert small_blocks == [{"interpret": True, "window": None, "live": True}]


def test_two_busy_slots_of_eight_answer_as_each_alone(small_blocks):
    """An engine through the kernel: two requests decode side by side in a
    replica of eight slots, six of which the kernel never visits, and
    their greedy answers are those of the same requests one at a time
    (then seven are left out)."""
    from ray_tpu.llm import DecodeEngine, LLMConfig, SamplingParams

    config = LLMConfig(
        vocab_size=300, max_seq_len=256, num_layers=2, num_heads=2,
        embed_dim=128, dtype="float32", max_batch_slots=8,
        prefill_buckets=(128,), prefix_cache_size=0)
    cfg = config.model_config()
    params = jax.tree.map(
        lambda a: a * 8 if a.ndim >= 2 else a,  # answers that move about
        module_for(cfg).init_params(cfg, jax.random.PRNGKey(3)))
    engine = DecodeEngine(config, params=params)
    rng = np.random.RandomState(3)
    # one prompt ends just short of a chunk's edge: its answer crosses it
    prompts = [[int(t) for t in rng.randint(2, 300, n)] for n in (125, 9)]
    greedy = SamplingParams(max_new_tokens=10)
    try:
        together = [engine.submit(p, greedy) for p in prompts]
        together = [list(f.result(timeout=300)) for f in together]
        stats = dict(engine.stats)
        alone = [list(engine.submit(p, greedy).result(timeout=300))
                 for p in prompts]
    finally:
        engine.shutdown()
    assert together == alone and len(set(map(tuple, alone))) == 2
    assert small_blocks == [{"interpret": True, "window": None, "live": True}]
    # both decoded in most ticks, and every slot of a tick is on one side
    assert stats["ticks"] < stats["slot_ticks"] <= 2 * stats["ticks"]
    assert stats["slots_skipped"] == 8 * stats["ticks"] - stats["slot_ticks"]
