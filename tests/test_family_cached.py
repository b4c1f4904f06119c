"""Every family's cached forward, by the engine's own programs and by hand,
once over the table of ``tests/families.py``: a prompt in padded chunks and
then cached steps against the plain reference, what a chunk boundary hands
on, what padding leaves alone, an idle slot beside a live one, the decode
kernels beside idle slots. Which families a case runs on is read off their
layer kinds (``families.shared_case``); the weights a server holds are
``test_family_weights.py``'s.

CPU, float32, seeded weights, tiny widths: no device number.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.engine import engine_programs
from ray_tpu.models import decoder, kv_cache
from tests import families
from tests.families import ROWS, shared_case

CHUNKS = {"one_bucket": [(16, 16)],
          "chunks": [(16, 16), (16, 16), (5, 8)]}


@functools.lru_cache(maxsize=None)
def _made(family, impl):
    return engine_programs(families.tiny_params(family)[0]), []


def _programs(family, impl, monkeypatch):
    """(the engine's programs of the row, one set a decode implementation,
    once a process; the row's check or None). A program is traced at its
    first call of a shape, in whichever case that falls: so every case
    comes by its programs here, where ``impl`` and the row's spies are put
    in place while it runs, and what they see is kept with the programs."""
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: impl)
    programs, seen = _made(family, impl)
    watch = ROWS[family].watch
    return programs, watch and watch(monkeypatch, impl, seen)


@functools.lru_cache(maxsize=None)
def _wanted(family, length):
    """(sequence, the reference's logits of it) of the row's weights."""
    sequence = families._tokens((length,), seed=2)
    _, params = families.tiny_params(family)
    return sequence, families._reference_logits(
        families.reference(family), params, sequence[None])[0]


@shared_case(names="family, layout, impl", rows=lambda family: [
    (family, layout, impl)
    for layout in ("chunks", "one_bucket")[:1 + families.has(family, "latent")]
    for impl in ("xla", "pallas_interpret")[:1 + families.has(
        family, "state|latent")]])
def test_padded_chunks_then_cached_steps_match_the_reference(
        family, layout, impl, monkeypatch):
    """A prompt of 37 tokens as two full chunks of 16 and 5 tokens padded
    to 8 (each starts from the states, and over the columns, rings and rows,
    the one before left; the last one's three padded steps must leave a
    state alone), and for a latent cache a prompt in one bucket (every
    position chosen), then 16 decode steps beside two idle slots. With
    ``pallas_interpret`` (for a family with a state or a latent cache: the
    kernels over keys and values alone are ``test_kv_cache.py``'s, a ring's
    ``test_afmoe.py``'s) every decode step is the family's decode kernels,
    and a chunk of 16 goes through its block kernels where its cache is
    whole tiles (the row's ``watch`` sees them)."""
    row = ROWS[family]
    programs, check = _programs(family, impl, monkeypatch)
    cfg, params = families.tiny_params(family)
    chunks = CHUNKS[layout]
    prompt = sum(n for n, _ in chunks)
    sequence, want = _wanted(family, prompt + 16)
    with jax.default_matmul_precision("highest"):
        rows, cache = families._prefill_then_decode(
            cfg, params, sequence, chunks, programs)
    if check:
        check(chunks, cache)
    at = list(np.cumsum([n for n, _ in chunks]) - 1) + list(
        range(prompt, prompt + 16))
    assert len(rows) == len(at)
    # float32 against float32, logits and not tokens
    gap = np.abs(np.stack(rows) - want[at]).max()
    assert gap < row.cached


@shared_case()
def test_a_cache_dropped_at_a_chunk_boundary_shows(family, monkeypatch):
    """The third chunk from an empty slot cache: the attention and latent
    layers see none of the first 32 positions, the state layers start from
    no state, and the last real token's logits are far from the
    reference's."""
    cfg, params = families.tiny_params(family)
    (prefill, *_), _ = _programs(family, "xla", monkeypatch)
    sequence, want = _wanted(family, 53)
    with jax.default_matmul_precision("highest"):
        fresh, _ = families.prefill_once(
            prefill, cfg, params, sequence[32:37], at=32, n=5, bucket=8)
    gap = np.abs(fresh - want[36]).max()
    assert gap > ROWS[family].dropped


@shared_case("state")
def test_padded_steps_leave_state_and_tail_as_the_last_real_token_did(
        family, monkeypatch):
    cfg, params = families.tiny_params(family)
    (prefill, *_), _ = _programs(family, "xla", monkeypatch)
    sequence = families._tokens((16,), seed=3)

    def state_after(n, bucket):
        return families.prefill_once(
            prefill, cfg, params, sequence, at=0, n=n, bucket=bucket)[1]

    padded, exact = state_after(5, 16), state_after(5, 8)
    for name in kv_cache.STATE:
        # two programs of two shapes: the same sums, perhaps not fused alike
        np.testing.assert_allclose(padded[name], exact[name], atol=1e-6)
    assert float(jnp.abs(padded["ssm"]).max()) > 1e-3
    # the tail is the last three rows that entered: not what padding made
    longer = state_after(8, 8)
    assert float(jnp.abs(longer["conv"] - exact["conv"]).max()) > 1e-3


def _three_ticks_of_slot_0(family, impl, monkeypatch):
    """Slot 0 of 3 decodes three tokens from length 20 over a cache of
    noise: (the cache before, the cache after, what the last tick
    counted, the slot's logits a tick). The caches as numpy's own copies:
    a tick takes its cache donated."""
    cfg, params = families.tiny_params(family)
    (_, _, decode, _), _ = _programs(family, impl, monkeypatch)
    rng = np.random.default_rng(4)
    cache = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        decoder.init_kv_cache(cfg, 3, 128, block=16))
    before = jax.tree.map(np.array, cache)
    packed = np.zeros((3, 3), np.int32)
    packed[:, 0] = 7, 20, 1      # slot 0 decodes at length 20
    ids, logits = jnp.zeros((3,), jnp.int32), []
    for _ in range(3):
        with jax.default_matmul_precision("highest"):
            ids, rows, cache, *counted = decode(
                params, ids, cache, jnp.asarray(packed))
        logits.append(np.asarray(rows[0]))
        packed[1, 0] += 1
    return before, jax.tree.map(np.asarray, cache), counted, np.stack(logits)


@shared_case("state")
def test_an_idle_slots_state_is_untouched_by_the_xla_step(
        family, monkeypatch):
    before, after, *_ = _three_ticks_of_slot_0(family, "xla", monkeypatch)
    for name in kv_cache.STATE:
        assert (after[name][:, 1:] == before[name][:, 1:]).all(), name
        assert np.abs(after[name][:, 0] - before[name][:, 0]).max() > 1e-3


@shared_case()
def test_the_decode_kernels_step_is_the_xla_step_and_skips_idle_slots(
        family, monkeypatch):
    """Three ticks of one live slot beside two idle ones through the
    family's decode kernels (``pallas_interpret``): the live slot's logits
    are the XLA step's, every leaf of its cache moved, and not a bit of an
    idle slot's, of any leaf: states, columns, rings, rows, keys."""
    *_, want = _three_ticks_of_slot_0(family, "xla", monkeypatch)
    before, after, counted, got = _three_ticks_of_slot_0(
        family, "pallas_interpret", monkeypatch)
    # float32 sums in another order: 1e-6 measured on logits of 0.1 to 0.7
    gap = np.abs(got - want).max(axis=-1)
    assert gap.max() < 2e-5, (gap, np.abs(want).max(-1), np.abs(got).max(-1))
    for name in before:
        assert np.abs(after[name][:, 0] - before[name][:, 0]).max() > 1e-3
        if name in kv_cache.WINDOW and before[name].shape[-1] % 128:
            continue    # a ring that is no whole lane tiles: the XLA step's
        assert (after[name][:, 1:] == before[name][:, 1:]).all(), name
    if counted and counted[0].ndim == 2:
        # a share's counts: experts touched and rows held a layer; a dense
        # layer none, a routed layer no more rows than the one token's pairs
        cfg, _ = families.tiny_params(family)
        counts, kinds = np.asarray(counted[0]), decoder.layer_kinds(cfg)
        assert counts.shape == (len(kinds), 2)
        for kind, count in zip(kinds, counts):
            assert count[1] <= cfg.moe.top_k if kind.routed else (
                not count.any()), kind


@shared_case()
def test_insert_writes_a_slot_of_leaves_of_every_rank(family, monkeypatch):
    cfg, _ = families.tiny_params(family)
    (_, insert, *_), _ = _programs(family, "xla", monkeypatch)
    rng = np.random.default_rng(5)
    batch = decoder.init_kv_cache(cfg, 3, 128, block=16)
    slot = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        decoder.init_kv_cache(cfg, 1, 128, block=16))
    out = insert(batch, slot, 2)
    for name, leaf in out.items():
        assert bool((leaf[:, 2] == slot[name][:, 0]).all()), name
        assert bool((leaf[:, :2] == 0).all()), name
