"""JAX decode engine: slot-based continuous batching on static shapes.

Reference analog: the vLLM engine behind ``ray/llm`` serving
(``_internal/serve/engines/vllm/``) — continuous batching, prefill/decode
split, KV cache management. TPU-first redesign instead of a port:

- The KV cache is one static [L, B, S, H, D] pytree; every decode tick is a
  single compiled XLA program over ALL active slots (MXU-batched), not a
  per-request loop.
- Prompts prefill at bucketed lengths (few compile variants) into a
  batch=1 cache, then a jitted insert writes the slot row — requests join
  and leave the running batch without recompiling (the "continuous" part).
  A prompt longer than the largest bucket prefills in chunks of that
  bucket, each continuing the cache of the one before (``start > 0``) in
  the same buffer (an admission's own slot cache is donated to the program;
  a prefix-cache entry's is shared, and the program copies it), all under
  the lock: no tick runs between two chunks. The prefill's head runs
  on the rows the host reads (the prompt's last, and the bucket boundaries
  the prefix store keeps), not on the whole bucket.
- Sampling happens host-side on the [B, V] logits of the tick (temperature
  / top-k / penalties / logprobs), which keeps the compiled program
  sampling-agnostic. A greedy row with nothing else for the host to do is
  its logits' argmax: the decode program takes it too, and takes the ids of
  the tick before as its input tokens without their leaving the chip. All
  the host sends a tick is ``packed`` [3, B] int32: each slot's token (-1:
  the chip's own), its length, and 1 where the slot decodes in this tick, 0
  where it does not: what the loop knows (its ``rows``), never read off a
  length. The model step keeps its hands off the cache of a slot that does
  not decode, and routes its row to no expert.
- So the loop runs one tick ahead of the host wherever every token the next
  tick needs is known, on the host or on the chip (``_step_locked``): it
  dispatches tick k+1, then reads tick k's ids. A row whose token the host
  has to choose from its logits holds the loop to today's order for the
  ticks it is in. An answer that ends on EOS or a stop token is seen one
  tick late; the row the tick in flight computed for it is thrown away
  (``overrun_rows``).

What the loop thread does is in the profiler's own trace, on the device's
timeline: ``engine.tick`` (children ``.pack``, ``.dispatch``, then ``.read``
of the tick before and its ``.sample``, or, with a host row, the tick's own
``.fetch`` and ``.sample``), ``engine.admit`` (children
``engine.admit.cache``, the empty slot cache of a prompt with no stored
prefix, ``engine.prefill.dispatch``, ``.fetch``, ``.sample``,
``engine.insert``), ``engine.finish`` and ``engine.idle`` are
``jax.profiler.TraceAnnotation`` spans, inert unless a capture runs (``rt
profile --xla``). A request's ledger is on its ``engine.finish``:
``queued_ms`` (``submit`` to the start of its admission, also on its
``engine.admit``), ``admit_ms`` (that to its first token: its own
admission), ``stalled_ms`` (every OTHER request's admission that ran while
its slot was active) and ``prompt_tokens`` beside ``first_token_ms``
(``queued_ms + admit_ms``) and ``total_ms``; what ``total_ms`` has beyond
the three is the request's decode ticks and their reads. ``held`` of
``engine.admit`` is how many slots were decoding and stood still for it;
``stalled_s`` of ``stats`` sums each admission's length times its ``held``
where ``admit_s`` sums the lengths, so ``stalled_s / admit_s`` slots wait
for an admission and ``queue_wait_s / requests`` is a request's wait for
its own. The answer carries ``time.monotonic()`` of its ``engine.finish``
(``GenerationResult.finished_at``, ``TokenStream.finished_at``) for the
serving layer's ``llm.done``. The other spans' arguments
are the counters of that boundary, and ``stats`` sums the same quantities
with no capture: ``cache_positions`` of ``engine.tick`` is how much of the
KV cache the tick needed (the active slots' lengths and the columns it
writes) of a layer that attends everything, ``cache_positions_full`` the
same where the model has such layers (``layers_full`` of them) and
``cache_positions_window`` what it needed of a window layer (each slot's
length or the window, whichever is less; ``layers_window``). ``active`` of
``slots`` decode in a tick, and the decode kernel visits those and no other
(``ops/decode_attention.py``): ``slots_skipped`` of ``stats`` sums the
visits a layer that it did not make, ``slots - active``, beside
``slot_ticks``, the ones it made. A prefill chunk attends the filled
positions of its slot's cache and no other (on a TPU through
``ops/block_attention.py``): ``prefill_key_positions`` of ``engine.admit`` is
the key positions the admission's chunks see, summed over chunks and
attention layers from their starts, their lengths and the window, beside
``prefill_cache_positions``, all that those layers' caches hold, once a
chunk; one less their ratio is the share of the cache a chunk is spared.
``tick``,
``active``, ``slots``, ``ahead``, ``overrun``, the ``cache_positions`` and
``moe_rows`` of an ``engine.tick`` span are those of the program
dispatched in it; ``experts_touched`` is the count of the programs read
since the tick span before: the tick before's (each ``.read`` and ``.fetch``
names its program by ``tick`` and carries its count) and, with a host row,
the span's own. One span is the loading thread's: ``engine.weights``, once
a replica, around the weights' arrival as the engine holds them.

A model with state layers (a recurrence in place of attention: Mamba-2's,
``ops/ssm.py``, or the gated delta rule's, ``ops/delta_rule.py``;
``models/kv_cache.py``) keeps a slot's states in the same cache pytree,
slot on axis 1: a prefill chunk starts from the state the chunk
before left in the slot cache and leaves the state after its last REAL token
(its programs take ``real`` as a router's do), ``insert`` writes the whole of
the admitted request's state over the last tenant's, and a tick reads and
writes the states of the slots that decode and of no other.
``state_slot_layers`` of ``engine.tick`` (slots that decode x ``layers_state``)
and ``ssm_prefill_tokens`` of ``engine.admit`` (real tokens the admission's
scans took, whichever recurrence scanned them: the name is the first one's)
are those counters, summed in ``stats`` under the same names; ``chunks`` of
``engine.admit`` says in how many programs, each from the state the one
before left. The
prefix store keeps whole prompts only, and prompt-lookup speculation is
refused at construction: a rejected draft's state cannot be rolled back.

A model with routed experts (``parallel/moe.py``) is told which rows of a
program carry a token (the slots that decode, a prompt's own positions in
its prefill bucket), so that nothing else is routed, and its programs hand
back, beside the logits and in the same read, how many distinct experts
received a row in each layer: ``experts_touched`` and ``moe_rows`` of
``engine.tick`` (with ``moe_layers``, to divide by) and ``engine.admit``,
``moe_experts_touched`` and ``moe_rows`` of ``stats``. A dense model's
programs are as they were. Where the layers hold a SHARE of the experts
(``MoEConfig.num_held``: one chip of an expert-parallel group) the programs
count, beside the experts touched, the pairs the held experts computed:
``moe_rows_held`` of ``engine.tick`` (of the programs read since the span
before, as ``experts_touched``) and of ``stats`` (the admissions' too).

A model with latent-attention layers (``models/kv_cache.py:attend_latent``)
keeps one row a position and layer in the same pytree; ``latent_positions``
of ``engine.tick`` is what the tick needed of it (``cache_positions`` a
latent layer), ``layers_full`` counts no such layer, and a chunk's
``prefill_key_positions`` count them as layers that attend everything.
Where those layers choose what they attend (a lightning indexer,
``decoder.Layer.index``), ``index_positions`` of ``engine.tick`` and
``engine.admit`` is what the indexer scored (each live slot's, or each of a
chunk's queries', visible positions, a latent layer) and
``selected_positions`` what attention was then asked to read (the kept
positions or the visible ones, whichever is fewer, a query and layer), and
``index_positions_read`` the positions of keys that the scores' kernel
visits for them where it runs (``ops/index_select.py:positions_read``, on
the host from the same lengths: whole blocks of a live slot's filled
positions in a tick, of what a tile of queries sees in a chunk; any other
backend's XLA scores every slot's whole leaf), so that ``index_positions /
index_positions_read`` is near 1 where only keys a query may see are read;
``sparse_tiles`` of ``engine.admit`` is the (sub-tile of queries, block of
positions) pairs the chunks' attention over the choice would visit with
every query reading every block up to the chunk's last token's, and
``sparse_tiles_computed`` the pairs it computes
(``ops/block_attention.py:selected_tiles``, on the host from the chunks'
starts and lengths: a sub-tile above the diagonal is left out), a head and
latent layer; ``stats`` sums the five, and ``engine.admit.cache`` carries
the ``bytes`` of the slot cache an admission fills.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ray_tpu._private.backoff import Backoff
from ray_tpu.llm.config import LLMConfig, load_tokenizer
from ray_tpu.util.debug import compile_count


@dataclass
class SamplingParams:
    """Per-request sampling controls (reference: vLLM SamplingParams —
    the engine_kwargs surface ray/llm passes through)."""

    max_new_tokens: int = 64
    temperature: float = 0.0   # 0 = greedy
    top_k: int = 0             # 0 = no top-k cut
    top_p: float = 1.0         # nucleus: smallest set with cumprob >= top_p
    min_p: float = 0.0         # keep tokens with prob >= min_p * max_prob
    repetition_penalty: float = 1.0   # HF-style, over prompt + generated
    presence_penalty: float = 0.0     # flat penalty on seen generated ids
    frequency_penalty: float = 0.0    # per-count penalty on generated ids
    logprobs: int = 0          # >0: return chosen + top-N logprobs/token
    seed: Optional[int] = None  # per-request determinism
    stop_token_ids: Sequence[int] = field(default_factory=tuple)
    stop: Sequence[str] = field(default_factory=tuple)  # string stops
    # EOS is a token like any other and the answer runs to its
    # ``max_new_tokens`` (vLLM's ``ignore_eos``): load of stated lengths
    ignore_eos: bool = False


class GenerationResult(list):
    """Generated token ids; quacks as the plain list older callers expect,
    with per-token logprob entries riding along when requested."""

    def __init__(self, token_ids, logprobs=None, finish_reason="",
                 finished_at=0.0):
        super().__init__(token_ids)
        self.logprobs = logprobs or []
        # how the answer ended: length | eos | stop | context
        self.finish_reason = finish_reason
        # time.monotonic() of its ``engine.finish``
        self.finished_at = finished_at


class TokenStream:
    """What ``submit_stream`` returns: an iterator over the generated token
    ids that knows, once exhausted, how the answer ended and when
    (``time.monotonic()`` of its ``engine.finish``)."""

    finish_reason = ""
    finished_at = 0.0

    def __init__(self, tokens, lock_wait_s: float = 0.0):
        self._tokens = tokens(self)
        # what ``submit_stream`` waited for the engine's lock
        self.lock_wait_s = lock_wait_s

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._tokens)


@dataclass
class _Slot:
    active: bool = False
    token_ids: List[int] = field(default_factory=list)
    prompt_len: int = 0
    produced: int = 0
    params: SamplingParams = field(default_factory=SamplingParams)
    future: Optional[Future] = None
    last_token: int = 0
    length: int = 0  # current absolute position (== tokens in cache)
    prompt_ids: List[int] = field(default_factory=list)  # penalties
    logprobs: List[dict] = field(default_factory=list)
    rng: Optional[Any] = None  # per-request RandomState when seed given
    stream_q: Optional[Any] = None  # queue.Queue for token streaming
    rid: str = ""  # the id the spans of this request share
    submitted: float = 0.0  # time.monotonic() at submit
    # the request's ledger, what ``engine.finish`` says of it: submit to the
    # start of its admission, that to its first token, and every other
    # request's admission that ran while this slot was active
    queued_ms: float = 0.0
    admit_ms: float = 0.0
    stalled_ms: float = 0.0


@dataclass
class _Pending:
    """A submitted request waiting for a slot."""

    kind: str  # "prompt" | "prefilled"
    payload: Any  # prompt ids | the transferred prefill state
    params: SamplingParams
    future: Future
    rid: str
    submitted: float
    admitted: float = 0.0  # time.monotonic() when its admission began


@dataclass
class _Tick:
    """A decode program that was dispatched and not read yet."""

    number: int
    rows: List[int]  # the slots that decode in it
    drafts: Dict[int, list]  # speculation: slot -> the tokens it verifies
    # some row's token is the host's to choose, from the row's logits
    host_rows: bool
    # its results, on the chip: each row's argmax [B] (None from
    # ``decode_all``), the logits, and the experts touched a layer as a
    # list of one or none
    ids: Any
    logits: Any
    touched: list


def _weights_facts(given, held) -> dict:
    """What ``engine.weights`` records of the weights a family's
    ``serving_params`` was ``given`` and of those it handed back: their
    bytes, and how many leaves are not the leaf given (0: nothing was to be
    rounded, and nothing ran). Arrays or a trace's values alike."""
    import jax

    given, held = jax.tree.leaves(given), jax.tree.leaves(held)

    def nbytes(leaves):
        return sum(a.size * a.dtype.itemsize for a in leaves)

    return {
        "given_bytes": nbytes(given), "held_bytes": nbytes(held),
        "leaves_rounded": sum(h is not g for g, h in zip(given, held)),
    }


def engine_programs(cfg, own_cache=False):
    """The engine's four XLA programs for model config ``cfg``: (prefill,
    insert, decode, decode_all), jitted and not yet compiled. ``params`` are
    the weights as the engine holds them (``DecodeEngine``). The cache is
    the model module's pytree with the slot on axis 1; ``insert`` and both
    decodes take it donated and give it back in the same buffer, and so
    does ``prefill`` with ``own_cache`` (a slot cache that is one
    admission's alone; a prefix-cache entry's is shared and never is). For a
    model with routed experts or state layers the three model programs are
    told how many of a row's tokens are tokens (``real`` [B]: one more
    argument; the last row of ``decode``'s ``packed``, which every model's
    has), and give one more result after the cache, the experts touched a
    layer [L] (zeros where no layer is routed)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.decoder import forward_cached, layer_kinds

    # routed experts route the rows that carry a token and no other, and a
    # state layer steps on those alone: either takes ``real``
    takes_real = any(kind.routed or kind.state is not None
                     for kind in layer_kinds(cfg))

    def prefill(params, tokens, cache1, start, *real, rows=None):
        # start > 0 = continuation from a cached prefix or from the chunk
        # before: only the prompt's tail runs through the model. ``rows``:
        # the tokens whose logits the host reads (None: every one)
        return forward_cached(
            params, tokens, cache1, start, cfg, *real, rows=rows)

    def insert(batch_cache, slot_cache, b):
        # the slot is axis 1 of every leaf, whatever its rank (keys and
        # values 5, a state layer's convolution rows 3)
        return jax.tree.map(
            lambda c, s1: jax.lax.dynamic_update_slice(
                c, s1.astype(c.dtype), (0, b) + (0,) * (c.ndim - 2)
            ),
            batch_cache, slot_cache,
        )

    def decode(params, before, cache, packed):
        # ``packed`` [3, B] int32 is all the host sends a tick: each slot's
        # token, its length and ``real``, 1 where the slot decodes and 0
        # where it does not: the rows routed to experts, and the slots
        # whose cache the step reads and writes. A token below 0 is not the
        # host's to give: it is the slot's own of ``before`` [B], the ids
        # the tick before chose, still on the chip.
        given, lens, real = packed
        tokens = jnp.where(given < 0, before, given)
        logits, *rest = forward_cached(
            params, tokens[:, None], cache, lens, cfg,
            *((real,) if takes_real else ()), live=real > 0)
        logits = logits[:, -1]
        # a greedy row's next token (the first index on a tie, as numpy's)
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (ids, logits, *rest)

    def decode_all(params, tokens, cache, lens, *real):
        # speculation verify: logits at EVERY position (position j's
        # row predicts the token after input j)
        return forward_cached(params, tokens, cache, lens, cfg, *real)

    return (
        jax.jit(prefill, donate_argnums=(2,) if own_cache else ()),
        jax.jit(insert, donate_argnums=(0,)),
        jax.jit(decode, donate_argnums=(2,)),
        jax.jit(decode_all, donate_argnums=(2,)),
    )


class DecodeEngine:
    """One replica's weights, cache, slots and loop.

    ``params`` (or the bundle at ``config.model_source``, or fresh weights
    from ``PRNGKey(seed)``) are the weights as they are made or loaded,
    ``param_dtype`` wide. The engine keeps ``self.params``: the family's
    ``serving_params`` of them, made once here (span ``engine.weights``:
    ``given_bytes``, ``held_bytes``, ``leaves_rounded``), and no other tree.
    A caller's own reference to what it gave is the caller's."""

    def __init__(self, config: LLMConfig, params=None, seed: int = 0):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import decoder, kv_cache, module_for

        self.config = config
        bundle = None
        if params is None and config.model_source:
            import pickle

            with open(config.model_source, "rb") as f:
                bundle = pickle.load(f)
            params = jax.tree.map(jnp.asarray, bundle["params"])
        cfg = self.model_config = config.model_config(bundle)
        # routed experts: the layers whose rows and touched experts the
        # programs report (0 = a dense model, whose programs report nothing)
        self._moe_layers = self._moe_top_k = 0
        if getattr(cfg, "moe", None) is not None:
            self._moe_top_k = cfg.moe.top_k
        model = module_for(cfg)
        kinds = decoder.layer_kinds(cfg)
        if self._moe_top_k:
            # the layers with routed experts (a model may lead with dense)
            self._moe_layers = sum(k.routed for k in kinds)
        # window layers: how many, and their window (0: none)
        self._layers_window = sum(k.window is not None for k in kinds)
        self._window = max((k.window or 0 for k in kinds), default=0)
        # state layers: what a slot carries through them is a state, whatever
        # its length (``models/kv_cache.py``)
        self._layers_state = sum(k.state is not None for k in kinds)
        # latent layers: one row a position that every head shares
        self._layers_latent = sum(k.latent is not None for k in kinds)
        # and the positions a query of theirs keeps, where an indexer
        # chooses them (0: every earlier one)
        self._index_kept = max(
            (k.index.kept for k in kinds if k.index is not None), default=0)
        # the layers hold a share of the experts: the programs count the
        # rows the held experts computed beside the experts touched
        self._moe_share = bool(self._moe_top_k) and (
            cfg.moe.num_held is not None)
        # the model programs take ``real``: a router's rows, a state's steps
        self._takes_real = bool(self._moe_layers or self._layers_state)
        self.tokenizer = load_tokenizer(config)
        self._span = jax.profiler.TraceAnnotation
        # The weights as the family's cached forward wants them of a caller
        # that runs it for every token: what it rounds to the activations'
        # dtype on every use, rounded once, here. Under a trace or on the
        # arrays themselves; a leaf the family hands back as it was given
        # costs no operation and no byte (bf16 experts stay where they lie).
        weights = {}

        def served(given):
            held = model.serving_params(cfg, given)
            weights.update(_weights_facts(given, held))
            return held

        if params is None:
            # One program: op by op, each parameter's RNG call compiles on
            # its own, and a cold replica of GPT-2-small spent 48 s there
            # on a v5e — more than serve.run() waits for a deployment. The
            # rounding is inside it, so the tree as it is initialised need
            # never be whole on the chip.
            params = jax.jit(
                lambda key: served(model.init_params(cfg, key))
            )(jax.random.PRNGKey(seed))
        else:
            params = served(params)
        with self._span("engine.weights", **weights):
            self.params = jax.block_until_ready(params)
        B, S = config.max_batch_slots, config.max_seq_len
        self._spec_k = max(
            0, int(getattr(config, "speculative_ngram_k", 0) or 0)
        )  # negatives = disabled, never a half-armed dispatch path
        if self._spec_k and self._layers_state:
            raise ValueError(
                "speculative_ngram_k > 0 with state layers: a rejected "
                "draft's tokens have stepped the state, and a state cannot "
                "be rolled back to the token before them (a column can be "
                "overwritten); it needs a snapshot a slot, which is not "
                "there")
        # the longest block of tokens one program writes: a window layer's
        # ring has room for it beside the window (``models/kv_cache.py``)
        block = max(*config.prefill_buckets, 1 + self._spec_k)
        self._cache = decoder.init_kv_cache(cfg, B, S, block=block)
        # what one slot holds of it: the cache an admission fills
        self._slot_cache_bytes = sum(
            leaf.nbytes // B for leaf in jax.tree.leaves(self._cache))
        self._layers_full = (
            len(kinds) - self._layers_window - self._layers_state
            - self._layers_latent)
        # (layers, positions held, window) of each kind of attention layer
        self._attended = [
            (layers, self._cache[name].shape[-1], window)
            for layers, name, window in (
                (self._layers_full, kv_cache.FULL[0], None),
                (self._layers_window, kv_cache.WINDOW[0], self._window),
                (self._layers_latent, kv_cache.LATENT, None))
            if layers]
        # the lengths short of a whole prompt that the prefix store keeps
        self._boundaries = tuple(config.prefill_buckets) if (
            config.prefix_cache_size > 0 and not self._layers_window
            and not self._layers_state) else ()
        self._rng = np.random.RandomState(seed)

        self._prefill, self._insert, self._decode, decode_all = (
            engine_programs(cfg))
        # the same program for a slot cache no prefix entry shares (a fresh
        # one, or the chunk before's): a chunk writes where the last wrote
        self._prefill_own = engine_programs(cfg, own_cache=True)[0]
        self._decode_spec = decode_all if self._spec_k > 0 else None
        self._empty_slot_cache = lambda: decoder.init_kv_cache(
            cfg, 1, S, block=block)
        # the ids the last ``decode`` chose, on the chip, and that program
        # while the host has not read it (the loop is then one tick ahead)
        self._ids = jnp.zeros((B,), jnp.int32)
        self._flying: Optional[_Tick] = None
        # experts touched that a read has counted and no ``engine.tick``
        # span carries yet: the next one does; the held experts' rows too
        self._touched_unspanned = 0
        self._held_unspanned = 0

        self._slots = [_Slot() for _ in range(B)]
        self._pending: "queue.Queue" = queue.Queue()
        self._loop_thread: Optional[threading.Thread] = None
        self._stopped = False
        self._lock = threading.Lock()
        # Automatic prefix cache: prompt-token tuple -> {"cache": slot-cache
        # pytree (immutable jax arrays — safe to share), "logits_row":
        # final-position logits for per-request sampling}. LRU-bounded;
        # entries are whole completed prefills.
        from collections import OrderedDict

        self._prefix_cache: "OrderedDict[tuple, dict]" = OrderedDict()
        # all numeric: replica_info() hands them out and a reader takes
        # the difference of every key between two moments
        self.stats = {
            "requests": 0, "tokens_generated": 0, "ticks": 0,
            # ticks dispatched while the tick before was unread, and rows
            # computed for a slot whose answer had ended in that tick:
            # slot_ticks == tokens_generated + overrun_rows (no speculation)
            "ticks_ahead": 0, "overrun_rows": 0,
            "prefix_hits": 0, "prefix_partial_hits": 0,
            "spec_proposed": 0, "spec_accepted": 0,
            # sums at the boundaries the spans mark: submit to admission,
            # the admissions themselves, active slots over ticks
            "queue_wait_s": 0.0, "admit_s": 0.0, "slot_ticks": 0,
            # each admission's length times the slots it held
            "stalled_s": 0.0,
            # slots that did not decode over ticks: the visits a layer the
            # decode kernel left out (slot_ticks: the ones it made)
            "slots_skipped": 0,
            # positions of the cache the ticks needed: the active slots'
            # lengths, each with the columns its tick writes
            "cache_positions": 0,
            # the same of a model's full layers, and of its window layers
            # (a slot's length or the window, whichever is less), a layer
            "cache_positions_full": 0, "cache_positions_window": 0,
            "finished_length": 0, "finished_eos": 0, "finished_stop": 0,
            "finished_context": 0,
            # routed (token, expert, layer) rows (real rows x k x layers) and
            # distinct experts that received one, summed over layers and
            # programs; both 0 for a dense model
            "moe_rows": 0, "moe_experts_touched": 0,
            # of those rows, the ones the experts held here computed (a
            # model whose layers hold a share of the experts; else 0)
            "moe_rows_held": 0,
            # positions of the latent layers' cache the ticks needed, summed
            # over those layers (0 for a model with none)
            "latent_positions": 0,
            # positions an indexer scored and positions attention was then
            # asked to read, ticks and admissions, summed over the latent
            # layers (0 for a model whose layers attend everything)
            "index_positions": 0, "selected_positions": 0,
            # positions of the indexer's keys that the scores' kernel visits
            # for them: whole blocks, of live slots and visible ones alone
            "index_positions_read": 0,
            # sub-tiles of queries x blocks of positions the chunks' sparse
            # attention would visit with every query reading every visible
            # block, and the ones it computes: none above the diagonal
            "sparse_tiles": 0, "sparse_tiles_computed": 0,
            # state layers: slots that decode x state layers over ticks (the
            # states a tick reads and writes), and prompt tokens that went
            # through a prefill's scan; both 0 for a model with none
            "state_slot_layers": 0, "ssm_prefill_tokens": 0,
            # key positions the prefill chunks visited, summed over chunks
            # and attention layers, and what those layers' caches hold (a
            # chunk scored against all of it before the block kernel)
            "prefill_key_positions": 0, "prefill_cache_positions": 0,
            "compiles": compile_count(),  # of the process, not the engine
        }
        self._rid_seq = itertools.count(1)

    # ------------------------------------------------------------- sampling

    def _rng_for(self, p: SamplingParams):
        return (np.random.RandomState(p.seed) if p.seed is not None
                else self._rng)

    def _sample(self, logits_row: np.ndarray, p: SamplingParams,
                prompt_ids: Sequence[int] = (),
                generated: Sequence[int] = (), rng=None):
        """(next_token, logprob_entry|None). Penalties -> temperature ->
        logprobs snapshot -> top-k/top-p/min-p truncation -> draw (the
        reported distribution is pre-truncation, vLLM's convention)."""
        logits = logits_row.astype(np.float64, copy=True)
        if p.repetition_penalty != 1.0:
            union = set(prompt_ids) | set(generated)
            seen = np.fromiter(union, dtype=np.int64, count=len(union))
            if seen.size:
                vals = logits[seen]
                logits[seen] = np.where(
                    vals > 0, vals / p.repetition_penalty,
                    vals * p.repetition_penalty,
                )
        if (p.presence_penalty or p.frequency_penalty) and generated:
            ids, counts = np.unique(
                np.asarray(generated, np.int64), return_counts=True
            )
            logits[ids] -= (
                p.presence_penalty + p.frequency_penalty * counts
            )
        greedy = p.temperature <= 0
        if not greedy:
            logits = logits / max(p.temperature, 1e-5)
        lp_entry = None
        if p.logprobs > 0:
            shifted = logits - logits.max()
            logps = shifted - np.log(np.exp(shifted).sum())
            n = min(p.logprobs, logps.shape[0])
            top = np.argpartition(logps, -n)[-n:]
            top = top[np.argsort(logps[top])[::-1]]
            lp_entry = {
                "top": [(int(t), float(logps[t])) for t in top],
                "logps": logps,  # chosen-token logprob filled by caller
            }
        if greedy:
            nxt = int(np.argmax(logits))
        else:
            k = min(p.top_k, logits.shape[0])  # request-controlled: clamp
            if k > 0:
                kth = np.partition(logits, -k)[-k]
                logits = np.where(logits < kth, -np.inf, logits)
            shifted = logits - logits.max()
            probs = np.exp(shifted)
            probs /= probs.sum()
            if p.top_p < 1.0:
                order = np.argsort(probs)[::-1]
                cum = np.cumsum(probs[order])
                # smallest prefix reaching top_p (always keep the head)
                cut = int(np.searchsorted(cum, p.top_p)) + 1
                mask = np.zeros_like(probs, dtype=bool)
                mask[order[:cut]] = True
                probs = np.where(mask, probs, 0.0)
                probs /= probs.sum()
            if p.min_p > 0.0:
                keep = probs >= p.min_p * probs.max()
                probs = np.where(keep, probs, 0.0)
                probs /= probs.sum()
            nxt = int((rng or self._rng).choice(len(probs), p=probs))
        if lp_entry is not None:
            lp_entry = {
                "token": nxt,
                "logprob": float(lp_entry["logps"][nxt]),
                "top_logprobs": lp_entry["top"],
            }
        return nxt, lp_entry

    # ------------------------------------------------------------ lifecycle

    def _bucket(self, n: int) -> int:
        for b in self.config.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(
            f"prompt length {n} exceeds largest prefill bucket "
            f"{max(self.config.prefill_buckets)}"
        )

    def _prefix_lookup_locked(self, prompt_ids):
        """(entry, matched_len): exact entry, the longest cached strict
        prefix, or (None, 0)."""
        key = tuple(prompt_ids)
        entry = self._prefix_cache.get(key)
        if entry is not None:
            self._prefix_cache.move_to_end(key)
            return entry, len(prompt_ids)
        best, best_len, best_key = None, 0, None
        for k, e in self._prefix_cache.items():
            n = len(k)
            if best_len < n < len(prompt_ids) and key[:n] == k:
                best, best_len, best_key = e, n, k
        if best_key is not None:
            # a hot shared prefix must stay resident under LRU pressure
            self._prefix_cache.move_to_end(best_key)
        return best, best_len

    def _prefix_lengths(self, n: int, base: int) -> set:
        """The lengths of a prompt of ``n`` tokens, prefilled from ``base``
        on, whose last row's logits the host reads: the prompt's own, and
        the bucket boundaries the prefix store keeps (system prompts shared
        by many requests match through these). A model with window or state
        layers keeps whole prompts only: a ring or a state that went on
        past a boundary is not that prefix's."""
        return {n} | {b for b in self._boundaries if base < b < n}

    def _prefix_store_locked(self, prompt_ids, cache1, logits_rows):
        """Store the prefixes of ``logits_rows`` (length -> the logits after
        its last token). All entries alias the same immutable cache
        pytree."""
        cap = self.config.prefix_cache_size
        if cap <= 0:
            return
        for ln, row in logits_rows.items():
            key = tuple(prompt_ids[:ln])
            self._prefix_cache[key] = {"cache": cache1, "logits_row": row}
            self._prefix_cache.move_to_end(key)
        while len(self._prefix_cache) > cap:
            self._prefix_cache.popitem(last=False)

    def _real(self, counts) -> tuple:
        """The model programs' last argument: how many of each row's tokens
        are tokens. A dense model's programs (attention in every layer, no
        router) take none."""
        import jax.numpy as jnp

        return (jnp.asarray(counts, jnp.int32),) if self._takes_real else ()

    def _moe_rows(self, real_rows: int) -> int:
        """A span's ``moe_rows``: what a program of ``real_rows`` rows that
        carry a token routes, summed into ``stats``."""
        rows = real_rows * self._moe_top_k * self._moe_layers
        self.stats["moe_rows"] += rows
        return rows

    def _state_count(self, name: str, count: int) -> dict:
        """A span's ``ssm_prefill_tokens`` (real tokens an admission's scans
        took) or ``state_slot_layers`` (slots that decode x state layers),
        with ``layers_state`` to divide by; summed into ``stats``. Nothing
        for a model without state layers."""
        if not self._layers_state:
            return {}
        self.stats[name] += count
        return {name: count, "layers_state": self._layers_state}

    def _prefill_positions(self, chunks) -> dict:
        """A span's ``prefill_key_positions`` of an admission's ``chunks``
        [(start, tokens with the padding)]: the key positions its programs
        visit (``kv_cache.positions_seen``), summed over chunks and
        attention layers, beside ``prefill_cache_positions``, all that those
        layers' caches hold, once a chunk; summed into ``stats``."""
        from ray_tpu.models import kv_cache
        from ray_tpu.ops import block_attention

        counts = {
            "prefill_key_positions": sum(
                layers * kv_cache.positions_seen(start, T, length, window)
                for start, T in chunks
                for layers, length, window in self._attended),
            "prefill_cache_positions": len(chunks) * sum(
                layers * length for layers, length, _ in self._attended)}
        if self._index_kept:
            seen = np.concatenate(
                [np.arange(start + 1, start + T + 1) for start, T in chunks]
                or [np.zeros(0, np.int64)])
            counts.update(self._chosen_positions(seen, chunks))
            tiles = [block_attention.selected_tiles(
                start, T, self.config.max_seq_len) for start, T in chunks]
            counts["sparse_tiles"] = self._layers_latent * sum(
                every for every, _ in tiles)
            counts["sparse_tiles_computed"] = self._layers_latent * sum(
                computed for _, computed in tiles)
        for name, count in counts.items():
            self.stats[name] += count
        return counts

    def _chosen_positions(self, seen, blocks) -> dict:
        """``index_positions`` and ``selected_positions`` of queries that
        see ``seen`` positions each, and ``index_positions_read`` of the
        ``blocks`` [(start, tokens)] of one slot each that they came in,
        over the latent layers."""
        from ray_tpu.ops import index_select

        return {
            "index_positions": int(seen.sum()) * self._layers_latent,
            "index_positions_read": sum(
                index_select.positions_read(
                    int(start), T, self.config.max_seq_len)
                for start, T in blocks) * self._layers_latent,
            "selected_positions": int(np.minimum(
                seen, self._index_kept).sum()) * self._layers_latent}

    def _experts_touched(self, touched) -> int:
        """A span's ``experts_touched`` of a program's count a layer, on the
        host (a list of one, or of none from a dense model), summed into
        ``stats``."""
        if not touched:
            return 0
        counts = np.asarray(touched[0])
        if counts.ndim == 2:    # a share: [layers, (touched, rows held)]
            held = int(counts[:, 1].sum())
            self.stats["moe_rows_held"] += held
            counts = counts[:, 0]
        experts = int(counts.sum())
        self.stats["moe_experts_touched"] += experts
        return experts

    def _prefill_locked(self, prompt_ids, params, rng=None):
        """(slot_cache jax pytree, first_token, first_logprob, how). Caller
        holds the lock. ``how`` is the admission span's ``bucket`` (0: no
        program ran; the last chunk's), ``chunks`` (programs run), ``prefix``
        (none | partial | exact), ``moe_rows`` and ``experts_touched`` (what
        its programs routed).
        Consults the prefix cache: an exact hit skips the model entirely; a
        strict-prefix hit prefills only the tail from the cached KV state."""
        import jax
        import jax.numpy as jnp

        span = self._span
        n = len(prompt_ids)
        # uniform length limit: acceptance must not depend on transient
        # prefix-cache residency
        if n >= self.config.max_seq_len:
            raise ValueError(
                f"prompt length {n} leaves no room for an answer in "
                f"max_seq_len {self.config.max_seq_len}")
        entry, matched = (
            self._prefix_lookup_locked(prompt_ids)
            if self.config.prefix_cache_size > 0
            else (None, 0)
        )
        if entry is not None and matched == n:
            self.stats["prefix_hits"] += 1
            with span("engine.prefill.sample"):
                first, lp = self._sample(
                    entry["logits_row"], params, prompt_ids, (), rng
                )
            return entry["cache"], first, lp, {
                "bucket": 0, "chunks": 0, "prefix": "exact", "moe_rows": 0,
                "experts_touched": 0, **self._prefill_positions([]),
                **self._state_count("ssm_prefill_tokens", 0)}
        largest = max(self.config.prefill_buckets)
        if entry is not None and n - matched <= largest and (
            matched + self._bucket(n - matched) > self.config.max_seq_len
        ):
            # the padded tail would reach past the end of the cache — full
            # prefill instead
            entry, matched = None, 0
        if entry is not None:
            self.stats["prefix_partial_hits"] += 1
        # base > 0 = continuation: only the prompt's tail runs, in chunks
        # of the largest bucket where it is longer than that
        base = matched
        wanted = sorted(self._prefix_lengths(n, base))
        # rows of one program's logits: as many as one chunk can be asked
        # for, a static shape (the unused ones repeat the chunk's last)
        width = 1 + len(self._boundaries)
        if entry is not None:
            cache1 = entry["cache"]
        else:
            with span("engine.admit.cache", bytes=self._slot_cache_bytes):
                cache1 = self._empty_slot_cache()
        programs = []  # (lengths read of it, its logits, its touched)
        chunks = []    # (where it starts, its tokens with the padding)
        with span("engine.prefill.dispatch"):
            for at in range(base, n, largest):
                piece = prompt_ids[at:at + largest]
                Tpad = self._bucket(len(piece))
                toks = np.zeros((1, Tpad), np.int32)
                toks[0, : len(piece)] = piece
                lengths = [ln for ln in wanted if at < ln <= at + len(piece)]
                rows = np.full((width,), len(piece) - 1, np.int32)
                rows[:len(lengths)] = [ln - at - 1 for ln in lengths]
                # a program's results are allocated as it is enqueued, and a
                # prompt's chunks are enqueued together: each would hold a
                # slot cache of its own, were this admission's not donated
                # (0.57 GB at 30 kv heads of 128 and 8,704 positions)
                shared = entry is not None and cache1 is entry["cache"]
                prefill = self._prefill if shared else self._prefill_own
                logits, cache1, *touched = prefill(
                    self.params, jnp.asarray(toks), cache1,
                    jnp.full((1,), at, jnp.int32),
                    *self._real([len(piece)]), rows=jnp.asarray(rows),
                )
                programs.append((lengths, logits, touched))
                chunks.append((at, Tpad))
        with span("engine.prefill.fetch"):
            # the wait for the programs and their [1, width, V] logits' way
            # to the host
            fetched = jax.device_get([p[1:] for p in programs])
            logits_rows = {
                ln: logits[0, i]
                for (lengths, *_), (logits, _) in zip(programs, fetched)
                for i, ln in enumerate(lengths)}
            moe = {"moe_rows": self._moe_rows(n - base),
                   "experts_touched": sum(
                       self._experts_touched(t) for _, t in fetched)}
        self._prefix_store_locked(prompt_ids, cache1, logits_rows)
        with span("engine.prefill.sample"):
            first, lp = self._sample(
                logits_rows[n], params, prompt_ids, (), rng
            )
        return cache1, first, lp, {
            "bucket": Tpad, "chunks": len(programs),
            "prefix": "partial" if base else "none", **moe,
            **self._prefill_positions(chunks),
            **self._state_count("ssm_prefill_tokens", n - base)}

    def _activate_slot_locked(self, b, cache1, first, req: _Pending,
                              prompt_len, prompt_ids=(), first_lp=None,
                              rng=None):
        with self._span("engine.insert"):
            self._cache = self._insert(self._cache, cache1, b)
        slot = self._slots[b]
        slot.active = True
        slot.token_ids = [first]
        slot.prompt_len = prompt_len
        slot.params = req.params
        slot.produced = 1
        slot.future = req.future
        slot.last_token = first
        slot.length = prompt_len
        slot.prompt_ids = list(prompt_ids)
        slot.logprobs = [first_lp] if first_lp is not None else []
        slot.rng = rng
        slot.stream_q = getattr(req.future, "_rt_stream_q", None)
        slot.rid, slot.submitted = req.rid, req.submitted
        if slot.stream_q is not None:
            slot.stream_q.put(first)
        slot.queued_ms = (req.admitted - req.submitted) * 1e3
        slot.admit_ms = (time.monotonic() - req.admitted) * 1e3
        slot.stalled_ms = 0.0
        self.stats["requests"] += 1
        self._finish_if_done_locked(b)

    def _admit_locked(self):
        free = [i for i, s in enumerate(self._slots) if not s.active]
        while free and not self._pending.empty():
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            b = free.pop(0)
            # the slots that decode stand still for this admission
            held = [s for s in self._slots if s.active]
            req.admitted = t0 = time.monotonic()
            queued = t0 - req.submitted
            try:
                with self._span(
                    "engine.admit", rid=req.rid,
                    queued_ms=round(queued * 1e3, 3),
                    prompt_tokens=self._prompt_len(req), slot=b,
                    held=len(held),
                ) as admit:
                    admit.set_metadata(**self._admit_one_locked(req, b))
            except Exception as e:
                # Admission failure (bad bucket, mismatched transferred
                # cache shapes, ...) surfaces on the caller's future, never
                # on other slots or the scheduler loop.
                req.future.set_exception(e)
                free.insert(0, b)
            # an admission holds every decoding slot for its whole length
            took = time.monotonic() - t0
            self.stats["queue_wait_s"] += queued
            self.stats["admit_s"] += took
            self.stats["stalled_s"] += took * len(held)
            for slot in held:
                slot.stalled_ms += took * 1e3
        self.stats["compiles"] = compile_count()

    @staticmethod
    def _prompt_len(req: _Pending) -> int:
        return (len(req.payload) if req.kind == "prompt"
                else int(req.payload["prompt_len"]))

    def _admit_one_locked(self, req: _Pending, b: int) -> dict:
        """Prefill (or take the transferred cache of) one request into slot
        ``b``; returns the admission span's ``bucket`` and ``prefix``."""
        import jax.numpy as jnp

        params, rng = req.params, None
        if req.kind == "prefilled":
            # PD disaggregation: the prompt's KV was computed by a
            # prefill server; insert its transferred cache directly.
            prefilled = req.payload
            cache1 = {
                k: jnp.asarray(v) for k, v in prefilled["cache"].items()
            }
            first = int(prefilled["first_token"])
            prompt_ids = tuple(prefilled.get("prompt_ids", ()))
            first_lp = prefilled.get("first_logprob")
            how = {"bucket": 0, "chunks": 0, "prefix": "none",
                   "moe_rows": 0, "experts_touched": 0,
                   **self._prefill_positions([]),
                   **self._state_count("ssm_prefill_tokens", 0)}
            if params.seed is not None:
                rng = self._rng_for(params)
                if params.temperature > 0:
                    # the prefill server consumed one draw from
                    # this seed sampling the first token; skip it
                    # or token 2 reuses token 1's random value
                    rng.random_sample()
        else:
            prompt_ids = req.payload
            if params.seed is not None:
                rng = self._rng_for(params)
            cache1, first, first_lp, how = self._prefill_locked(
                prompt_ids, params, rng
            )
        prompt_len = self._prompt_len(req)
        if prompt_len <= 0:
            raise ValueError("prompt must be non-empty")
        self._activate_slot_locked(
            b, cache1, first, req, prompt_len,
            prompt_ids=prompt_ids, first_lp=first_lp, rng=rng,
        )
        return how

    def _stop_tokens(self, params: SamplingParams) -> set:
        """The tokens that end a request's answer and are no part of it."""
        return set(params.stop_token_ids) | (
            set() if params.ignore_eos else {self.tokenizer.eos_id})

    def _finish_if_done_locked(self, b: int):
        slot = self._slots[b]
        stop = self._stop_tokens(slot.params)
        out = None
        # the first that holds names how the answer ended
        reason = (
            "eos" if slot.last_token == self.tokenizer.eos_id
            and not slot.params.ignore_eos
            else "stop" if slot.last_token in stop
            else "length" if slot.produced >= slot.params.max_new_tokens
            else "context" if slot.length + 1 >= self.config.max_seq_len
            else None
        )
        if slot.params.stop:
            # Runs even when another criterion already fired: the final
            # token can both complete a stop needle and hit max_new_tokens,
            # and the needle must still be trimmed. String stops match on
            # the DECODED text (a stop may span token boundaries);
            # O(len^2) worst case over a request, bounded by
            # max_new_tokens.
            text = self.tokenizer.decode(slot.token_ids)
            for needle in slot.params.stop:
                idx = text.find(needle)
                if idx >= 0:
                    # trim to the tokens whose decode stays before the stop
                    keep = len(slot.token_ids)
                    while keep > 0 and len(
                        self.tokenizer.decode(slot.token_ids[:keep])
                    ) > idx:
                        keep -= 1
                    out = slot.token_ids[:keep]
                    reason = "stop"
                    break
        if reason is None:
            return
        now = time.monotonic()
        with self._span(
            "engine.finish", rid=slot.rid, produced=slot.produced,
            reason=reason, prompt_tokens=slot.prompt_len,
            queued_ms=round(slot.queued_ms, 3),
            admit_ms=round(slot.admit_ms, 3),
            stalled_ms=round(slot.stalled_ms, 3),
            first_token_ms=round(slot.queued_ms + slot.admit_ms, 3),
            total_ms=round((now - slot.submitted) * 1e3, 3),
        ):
            if out is None:
                out = slot.token_ids
                if out and out[-1] in stop:
                    out = out[:-1]
            if slot.stream_q is not None:
                slot.stream_q.put(("__done__", len(out), reason, now))
            if slot.future is not None:
                slot.future.set_result(GenerationResult(
                    out, slot.logprobs[: len(out)], reason, now
                ))
            slot.active = False
            slot.future = None
            self.stats["finished_" + reason] += 1

    # ------------------------------------------------------- the tick loop

    def _chip_row(self, p: SamplingParams) -> bool:
        """Whether a request's every next token is its logits' argmax and
        nothing else, so that the decode program's ``ids`` hold it and the
        next tick can take it from there. Anything the host has to do with
        a row's logits first (a draw, a penalty over the history,
        ``logprobs``, speculation's drafts) makes it a host row."""
        return (not self._spec_k and p.temperature <= 0 and p.logprobs <= 0
                and p.repetition_penalty == 1.0
                and not p.presence_penalty and not p.frequency_penalty)

    def _ends_unread(self, slot: _Slot) -> bool:
        """Whether the token a slot makes in the tick in flight is known to
        be its last: the host counts ``produced`` and ``length`` itself."""
        return (slot.produced + 1 >= slot.params.max_new_tokens
                or slot.length + 2 >= self.config.max_seq_len)

    def _step_locked(self) -> bool:
        """One turn of the loop; False where there was nothing to do.
        Admit, dispatch the next tick, then read the tick before it: the
        loop is one tick ahead of the host wherever the tokens the next
        tick needs are known without reading anything. They always are for
        the slots of the tick in flight: a tick that holds a host row is
        read in its own turn, and an admission reads first."""
        if not (self._pending.empty() or all(s.active for s in self._slots)):
            # no token waits behind a prefill, and a slot is filled only
            # when every program that decodes it was read
            self._read_locked(self._flying)
            self._admit_locked()
        flying = self._flying
        rows = [i for i, s in enumerate(self._slots) if s.active and not (
            flying is not None and i in flying.rows
            and self._ends_unread(s))]
        if rows:
            self._tick_locked(rows)
        elif flying is None:
            return False
        else:
            self._read_locked(flying)
        return True

    # ------------------------------------------- prompt-lookup speculation

    def _propose_draft(self, slot, k: int):
        """Prompt-lookup proposal (vLLM "[ngram]" speculator): find the
        most recent earlier occurrence of the current 2-gram (then 1-gram)
        in prompt+generated history and copy its continuation."""
        hist = slot.prompt_ids + slot.token_ids
        # bounded lookback (vLLM [ngram] caps this too): an O(full-history)
        # scan per token would serialize long-context decode on the host
        window = 512
        if len(hist) > window:
            hist = hist[-window:]
        L = len(hist)
        for n in (2, 1):
            if L <= n:
                continue
            pat = hist[-n:]
            for i in range(L - n - 1, -1, -1):
                if hist[i:i + n] == pat:
                    # i <= L-n-1 guarantees a non-empty continuation
                    return hist[i + n:i + n + k]
        return []

    def _drafts_locked(self, rows) -> Dict[int, list]:
        """Speculation: up to k drafted tokens per GREEDY slot, verified in
        ONE dispatch (accepted prefix + one corrected token all come from
        the same logits). Stochastic slots ride along with draft length 0.
        Cache safety: forward_cached writes K/V before attending and masks
        keys beyond each query position, and later writes overwrite
        rejected-draft positions — stale KV can never be attended. No
        draft, a plain tick: the (1+K)-wide dispatch would pay ~K x
        attention/logits cost for zero benefit, and near the sequence end
        its [B, 1+K] write would reach past the end of the cache."""
        K, S = self._spec_k, self.config.max_seq_len
        drafts: Dict[int, list] = {}
        if any(self._slots[i].length + 1 + K > S for i in rows):
            return drafts
        for i in rows:
            slot = self._slots[i]
            if slot.params.temperature <= 0:
                d = self._propose_draft(slot, K)
                if d:
                    drafts[i] = d
                    self.stats["spec_proposed"] += len(d)
        return drafts

    # ------------------------------------------------------------- one tick

    def _tick_locked(self, rows) -> None:
        """One decode program over every slot, between its spans: pack
        what the host knows of ``rows`` (a slot's last token, or "the
        chip's" where the tick in flight makes it; its length; its draft),
        dispatch, then read the tick in flight, whose results the program
        just dispatched no longer waits for. A tick with a host row is
        read in its own span: its tokens are the next tick's input."""
        import jax.numpy as jnp

        span = self._span
        flying = self._flying
        drafts = self._drafts_locked(rows) if self._spec_k else {}
        compiles = self.stats["compiles"]
        B = len(self._slots)
        with span("engine.tick", tick=self.stats["ticks"], active=len(rows),
                  slots=B, moe_layers=self._moe_layers,
                  ahead=int(flying is not None)) as tick:
            with span("engine.tick.pack"):
                toks = np.zeros((B, 1 + self._spec_k if drafts else 1),
                                np.int32)
                lens = np.zeros((B,), np.int32)
                real = np.zeros((B,), np.int32)
                for i in rows:
                    slot = self._slots[i]
                    unread = flying is not None and i in flying.rows
                    toks[i, :] = -1 if unread else slot.last_token
                    lens[i] = slot.length + unread
                    d = drafts.get(i, ())
                    toks[i, 1:1 + len(d)] = d
                    real[i] = 1 + len(d)
                needed = lens + real     # a slot's length and its columns
                cache_positions = int(needed.sum())
                positions = {
                    "cache_positions_full":
                        cache_positions if self._layers_full else 0,
                    "cache_positions_window": int(np.minimum(
                        needed, self._window).sum())}
                if self._layers_latent:
                    positions["latent_positions"] = (
                        cache_positions * self._layers_latent)
                if self._index_kept:
                    positions.update(self._chosen_positions(
                        needed[list(rows)], [(lens[i], 1) for i in rows]))
                if drafts:
                    sent = (jnp.asarray(toks), jnp.asarray(lens),
                            *self._real(real))
                else:
                    sent = jnp.asarray(np.stack([toks[:, 0], lens, real]))
            with span("engine.tick.dispatch"):
                if drafts:
                    ids = None
                    logits, self._cache, *touched = self._decode_spec(
                        self.params, sent[0], self._cache, *sent[1:])
                else:
                    ids, logits, self._cache, *touched = self._decode(
                        self.params, self._ids, self._cache, sent)
                    self._ids = ids
                    for small in (ids, *touched):
                        small.copy_to_host_async()
            self._flying = now = _Tick(
                self.stats["ticks"], rows, drafts,
                any(not self._chip_row(self._slots[i].params) for i in rows),
                ids, logits, touched)
            self.stats["ticks"] += 1
            self.stats["ticks_ahead"] += flying is not None
            self.stats["slot_ticks"] += len(rows)
            self.stats["slots_skipped"] += B - len(rows)
            self.stats["cache_positions"] += cache_positions
            for name, count in positions.items():
                self.stats[name] += count
            self.stats["compiles"] = compile_count()
            moe_rows = self._moe_rows(int(real.sum()))
            state = self._state_count(
                "state_slot_layers", len(rows) * self._layers_state)
            self._read_locked(flying)
            # a row of this program whose answer ended in that read
            overrun = sum(not self._slots[i].active for i in rows)
            self.stats["overrun_rows"] += overrun
            if now.host_rows:
                self._read_locked(now)
            # ``experts_touched``: of the programs read since the tick span
            # before, that is the tick before's and, with a host row, this
            # one's own
            tick.set_metadata(
                compiled=int(self.stats["compiles"] > compiles),
                cache_positions=cache_positions, overrun=overrun,
                moe_rows=moe_rows, experts_touched=self._touched_unspanned,
                layers_full=self._layers_full,
                layers_window=self._layers_window, **positions, **state,
                **({"moe_rows_held": self._held_unspanned}
                   if self._moe_share else {}))
            self._touched_unspanned = self._held_unspanned = 0

    def _read_locked(self, tick: Optional[_Tick]) -> None:
        """Bring ``tick``'s results to the host and do its rows' bookkeeping
        (None: nothing is in flight). The wait for the program, then its
        ids' way to the host (``engine.tick.read``: 40-64 bytes whose copy
        began at the dispatch) or, where a row's token is the host's to
        choose, its logits' (``engine.tick.fetch``)."""
        import jax

        if tick is None:
            return
        if self._flying is tick:
            self._flying = None
        with self._span("engine.tick.fetch" if tick.host_rows
                        else "engine.tick.read", tick=tick.number) as read:
            ids, logits, touched = jax.device_get((
                tick.ids, tick.logits if tick.host_rows else None,
                tick.touched))
            held = self.stats["moe_rows_held"]
            experts = self._experts_touched(touched)
            self._touched_unspanned += experts
            self._held_unspanned += self.stats["moe_rows_held"] - held
            read.set_metadata(experts_touched=experts)
        with self._span("engine.tick.sample"):
            for i in tick.rows:
                slot = self._slots[i]
                if not slot.active:
                    # its answer ended in the tick before this one: the row
                    # is thrown away (counted as ``overrun`` of this tick)
                    continue
                if self._chip_row(slot.params):
                    self._emit_token_locked(i, int(ids[i]), None)
                    continue
                draft = tick.drafts.get(i, ())
                # [1 + K, V]: position j's row predicts the token after
                # input j
                at = logits[i].reshape(-1, logits.shape[-1])
                for j in range(len(draft) + 1):
                    nxt, lp = self._sample(
                        at[j], slot.params, slot.prompt_ids,
                        slot.token_ids, slot.rng,
                    )
                    self._emit_token_locked(i, nxt, lp)
                    if not slot.active:
                        break  # finished mid-run (stop/max/length)
                    if j < len(draft):
                        if nxt != draft[j]:
                            break  # mismatch: later logits had wrong context
                        self.stats["spec_accepted"] += 1

    def _emit_token_locked(self, i: int, nxt: int, lp) -> None:
        """Per-token bookkeeping, whoever chose the token."""
        slot = self._slots[i]
        slot.token_ids.append(nxt)
        if lp is not None:
            slot.logprobs.append(lp)
        if slot.stream_q is not None:
            slot.stream_q.put(nxt)
        slot.last_token = nxt
        slot.produced += 1
        slot.length += 1
        self.stats["tokens_generated"] += 1
        self._finish_if_done_locked(i)

    # ------------------------------------------------------------- public

    def _enqueue(self, kind: str, payload, params, fut: Future,
                 rid) -> float:
        """Queue a request and see that the loop runs. Returns the seconds
        the caller then waited for the engine's lock, which the loop holds
        for a whole turn (``lock_wait_ms`` of ``llm.request``). The request
        is stamped ``submitted`` and queued first, so the wait is the
        CALLER's and runs beside the request's ``engine.finish``
        ``total_ms``, not on top of it (a running loop takes the request up
        at its next turn, whoever holds the lock). Where it outlasts the
        engine's work the answer waits for it: the caller reads its first
        token only after this returns, and the excess shows as ``llm.done``'s
        ``after_finish_ms``. A sum over a request's way counts ``total_ms``
        and ``after_finish_ms``, and not this."""
        if rid is None:
            # submitted to the engine directly: an engine-local number
            rid = f"engine-{next(self._rid_seq)}"
        self._pending.put(_Pending(
            kind, payload, params or SamplingParams(), fut, rid,
            time.monotonic()))
        return self._ensure_loop()

    def submit(self, prompt_ids: List[int],
               params: Optional[SamplingParams] = None,
               rid: Optional[str] = None) -> Future:
        """Continuous-batching entry: returns a Future of generated ids.
        ``rid`` is the id the request's spans carry (the serving layer's
        ``cmpl-...``)."""
        if not prompt_ids:
            raise ValueError("prompt must be non-empty")
        fut: Future = Future()
        fut.lock_wait_s = self._enqueue(
            "prompt", list(prompt_ids), params, fut, rid)
        return fut

    def prefill_only(self, prompt_ids: List[int],
                     params: Optional[SamplingParams] = None) -> dict:
        """Prefill-server half of PD disaggregation (reference:
        ``serving_patterns/prefill_decode/builder.py``): compute the
        prompt's KV cache + first token WITHOUT occupying a decode slot.
        Returns a transferable dict a decode engine resumes from."""
        if not prompt_ids:
            raise ValueError("prompt must be non-empty")
        params = params or SamplingParams()
        with self._lock:
            cache1, first, lp, _ = self._prefill_locked(
                list(prompt_ids), params, self._rng_for(params)
            )
            return {
                "cache": {k: np.asarray(v) for k, v in cache1.items()},
                "first_token": first,
                "prompt_len": len(prompt_ids),
                "first_logprob": lp,
                # penalties need the prompt on the DECODE side too
                "prompt_ids": list(prompt_ids),
            }

    def submit_prefilled(self, prefilled: dict,
                         params: Optional[SamplingParams] = None) -> Future:
        """Decode-server half of PD disaggregation: continue generation from
        a transferred prefill state."""
        fut: Future = Future()
        self._enqueue("prefilled", prefilled, params, fut, None)
        return fut

    def submit_stream(self, prompt_ids: List[int],
                      params: Optional[SamplingParams] = None,
                      rid: Optional[str] = None) -> TokenStream:
        """Token-level streaming (reference: vLLM streaming generation /
        OpenAI stream=true). Yields generated token ids as the decode loop
        produces them; raises the request's error if admission fails.

        Stop-token trimming is reflected (the trimmed token is simply not
        yielded); string stops are NOT supported here — their trim point
        is only known at the end, so such requests must use submit()
        (the serving layer enforces this split)."""
        if params and params.stop:
            raise ValueError(
                "string stops are not streamable; use submit()"
            )
        import queue as _q

        fut: Future = Future()
        q: "_q.Queue" = _q.Queue()
        fut._rt_stream_q = q
        lock_wait_s = self._enqueue(
            "prompt", list(prompt_ids), params, fut, rid)

        def gen(stream: TokenStream):
            while True:
                if fut.done() and fut.exception() is not None:
                    raise fut.exception()
                try:
                    item = q.get(timeout=1.0)
                except _q.Empty:
                    continue
                if isinstance(item, tuple) and item[0] == "__done__":
                    stream.finish_reason, stream.finished_at = item[2:]
                    return
                # a stop TOKEN ends the request without being part of the
                # output; the done marker's kept-length already excludes
                # it, so check before yielding
                if item in self._stop_tokens(params or SamplingParams()):
                    continue  # await the done marker
                yield item

        return TokenStream(gen, lock_wait_s)

    def generate(self, prompt_ids: List[int],
                 params: Optional[SamplingParams] = None) -> List[int]:
        """Synchronous single-request generation (batch path)."""
        return self.submit(prompt_ids, params).result(timeout=600)

    def generate_text(self, prompt: str,
                      params: Optional[SamplingParams] = None) -> str:
        ids = self.tokenizer.encode(prompt)
        out = self.generate(ids, params)
        return self.tokenizer.decode(out)

    def _ensure_loop(self) -> float:
        """Start the loop's thread where none runs; the seconds the caller
        waited for the lock."""
        asked = time.monotonic()
        with self._lock:
            waited = time.monotonic() - asked
            if self._loop_thread is None or not self._loop_thread.is_alive():
                self._stopped = False
                self._loop_thread = threading.Thread(
                    target=self._loop, daemon=True, name="rt-llm-engine"
                )
                self._loop_thread.start()
        return waited

    def _loop(self):
        idle_since = None
        # Jittered tick: 2ms while work flows (reset below), backing
        # off to 20ms when idle so the park check isn't a busy spin.
        tick = Backoff(base=0.002, cap=0.02)
        while not self._stopped:
            try:
                with self._lock:
                    busy = self._step_locked()
            except Exception as e:
                # Never die holding unresolved futures: fail every in-flight
                # request, clear the slots, keep serving.
                with self._lock:
                    self._flying = None
                    for slot in self._slots:
                        if slot.active and slot.future is not None:
                            slot.future.set_exception(e)
                        slot.active = False
                        slot.future = None
                busy = False
            if busy or not self._pending.empty():
                idle_since = None
                tick.reset()
                continue
            if idle_since is None:
                idle_since = time.monotonic()
            elif time.monotonic() - idle_since > 30:
                # Park. The pending re-check + handoff under the lock closes
                # the race with a submit() that saw this thread still alive.
                with self._lock:
                    if self._pending.empty():
                        self._loop_thread = None
                        return
                idle_since = None
            # the chip waits for a request here, not for the host
            with self._span("engine.idle"):
                tick.sleep()

    def shutdown(self):
        self._stopped = True
        t = self._loop_thread
        if t is not None:
            t.join(timeout=5)
