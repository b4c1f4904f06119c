"""The main path's kernels compile for the real chip — without the chip.

The TPU compiler is installed here and compiles for a device that is
described (``v5e:2x2``) and not attached, so a Mosaic kernel the chip would
refuse (misaligned slice, too much VMEM, unpartitionable call) fails here at
no chip time. Nothing runs: this says nothing about results or speed.

The topology is described inside a module-scoped fixture of THIS file only
(never at import, never autouse, never in conftest): describing it loads the
TPU library, which one process at a time may hold, and xdist workers import
every test file. Keep every such test in this one file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

from ray_tpu.ops import grouped_matmul, rows_to_tokens
from ray_tpu.ops.attention import attention, flash_attention
from ray_tpu.parallel import moe


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without a chip; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _flash(q, k, v):
    return flash_attention(q, k, v, True)  # blocks: the kernel's choice


def _flash_grads(q, k, v):
    return jax.grad(
        lambda q, k, v: _flash(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)


# GPT-2-small at the smoke/bench batch, GPT-2-medium, and head-dim 128:
# [B, H, T, D], heads-major as the attention ops take them.
WIDTHS = [(32, 12, 1024, 64), (16, 16, 1024, 64), (4, 16, 2048, 128)]


@pytest.mark.parametrize("shape", WIDTHS, ids=str)
@pytest.mark.parametrize("fn,kernels", [(_flash, 1), (_flash_grads, 2)],
                         ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles_for_v5e(one_chip, shape, fn, kernels):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(fn).lower(x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == kernels


@pytest.mark.parametrize("blocks", [(None, None), (512, 512), (256, 256)],
                         ids=str)
def test_flash_calls_are_what_the_roofline_reader_looks_for(one_chip, blocks):
    """``benchmarks/lib/kernels.py`` knows the flash kernels by their call
    alone: three operands is the forward, four or more the backward (ONE
    call), and every Pallas call's first result is [batch x heads, seq, head
    size]. A change that would silence the rooflines fails here, not on the
    chip."""
    from benchmarks.lib import kernels, trace

    B, H, T, D = WIDTHS[1]
    x = jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16, sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(
            lambda q, k, v: flash_attention(q, k, v, True, *blocks)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(x, x, x).compile().as_text()
    calls = [line for line in text.splitlines() if trace.is_kernel(line)]
    assert sorted(trace.operand_count(c) for c in calls) == [3, 6]
    for call in calls:
        assert kernels.FIRST_RESULT.search(call).group(1) == f"{B * H},{T},{D}"


def test_flash_attention_compiles_inside_a_sharded_jit(topo):
    """The ``--chips 4`` path: on an fsdp=2 x tensor=2 mesh the dispatcher
    wraps the kernel in shard_map (GSPMD cannot partition a Mosaic call),
    each chip taking its shard of batch and heads."""
    import numpy as np

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("fsdp", "tensor"))
    spec = NamedSharding(mesh, P("fsdp", "tensor", None, None))
    x = jax.ShapeDtypeStruct((32, 12, 1024, 64), jnp.bfloat16, sharding=spec)
    fn = functools.partial(attention, causal=True, impl="flash", mesh=mesh)
    compiled = jax.jit(fn).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.output_shardings.is_equivalent_to(spec, 4)
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(_flash).lower(x, x, x).compile()


def test_flash_attention_compiles_inside_a_pipeline_stage(topo):
    """``pipeline_apply`` is a shard_map that holds ``stage``; the
    dispatcher's own shard_map nests inside it on the axes still free
    (here ``data``), so a pipelined step keeps the Mosaic kernel."""
    import numpy as np

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("stage", "data"))
    spec = NamedSharding(mesh, P("data", None, None, None))
    x = jax.ShapeDtypeStruct((32, 12, 1024, 64), jnp.bfloat16, sharding=spec)
    stage = jax.shard_map(
        functools.partial(attention, causal=True, impl="flash", mesh=mesh),
        mesh=mesh, axis_names={"stage"}, in_specs=(P(), P(), P()),
        out_specs=P(), check_vma=False,
    )
    compiled = jax.jit(stage).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.output_shardings.is_equivalent_to(spec, 4)


# ---------------------------------------------------------------- the engine
# The serving cell's size: GPT-2 XL, 10 slots, 1024 positions.


def _engine_program_args(one_chip, slots, width, cfg=None, block=1):
    """Shapes on the described chip of what an engine program of ``cfg``
    (default: GPT-2 XL) takes: params as the engine holds them (the
    family's ``serving_params`` of what it initialises), tokens, cache,
    start, and for a model with routed experts the rows that carry a
    token."""
    from ray_tpu.models import gpt2, module_for

    cfg = cfg or gpt2.GPT2_XL
    model = module_for(cfg)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: model.serving_params(
        cfg, model.init_params(cfg, jax.random.PRNGKey(0)))))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_kv_cache(cfg, slots, cfg.max_seq_len,
                                    block=block)))
    tokens = jax.ShapeDtypeStruct((slots, width), jnp.int32,
                                  sharding=one_chip)
    start = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    from ray_tpu.models.decoder import layer_kinds

    takes_real = getattr(cfg, "moe", None) is not None or any(
        k.state is not None for k in layer_kinds(cfg))
    real = (start,) if takes_real else ()
    return cfg, (params, tokens, cache, start, *real)


def _decode_args(one_chip, args):
    """``jit_decode``'s arguments from a model program's at width 1: the
    ids of the tick before [slots], and the host's one packed array of
    tokens, lengths and ``real`` in the tokens' place."""
    params, _, cache, start, *_ = args
    packed = jax.ShapeDtypeStruct((3, *start.shape), jnp.int32,
                                  sharding=one_chip)
    return params, start, cache, packed


def _weight_converts(hlo_text, params):
    """Every ``convert`` in the optimized HLO (an instruction of its own or
    the root a fusion is named after) whose result has the dimensions of a
    stacked block weight or of an embedding table: a program that rounds a
    weight it was given."""
    import re

    shapes = {",".join(map(str, a.shape)) for a in jax.tree.leaves(params)
              if a.ndim >= 2 and a.size >= 1 << 20}
    assert len(shapes) >= 5
    found = []
    for line in hlo_text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%?(convert[\w.\-]*) = \w+\[([\d,]*)\]", line)
        if m and m.group(2) in shapes:
            found.append((m.group(1), m.group(2)))
    return found


def _cache_sized(hlo_text, cache, ops="copy|transpose"):
    """(computation, op, shape) of every ``ops`` instruction in the
    optimized HLO whose dimensions are a layer's slice of the cache or the
    whole of it, in any order."""
    import re

    L, B, KV, D, S = cache["k"].shape
    want = {tuple(sorted(d for d in dims if d > 1))
            for dims in ((B, KV, D, S), (L, B, KV, D, S))}
    found, computation = [], None
    for line in hlo_text.splitlines():
        if line.startswith(("%", "ENTRY")):
            computation = line.split(" ", 1)[0]  # "ENTRY" or its %name
        m = re.match(
            r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* (" + ops + r")\(",
            line)
        if m:
            dims = tuple(sorted(
                int(d) for d in m.group(1).split(",") if d and int(d) > 1))
            if dims in want:
                found.append((computation, m.group(2), m.group(1)))
    return found


def _block_attention(text, cache):
    """The ``block_attention`` custom calls of a compiled ``jit_prefill``,
    after holding the program to what a chunk's attention may leave in it:
    an array with a dimension of a cache's length and more elements than a
    layer's view of that cache is the cache leaf: no ``[heads, T, S]``
    scores, no one-hot placement."""
    import math
    import re

    leaves = [leaf.shape for name, leaf in cache.items()
              if name in ("k", "v", "k_window", "v_window")]
    held = {tuple(int(d) for d in dims.split(","))
            for dims in re.findall(r"= \w+\[([\d,]+)\]", text)}
    for leaf in leaves:
        assert {shape for shape in held if leaf[-1] in shape
                and math.prod(shape) > math.prod(leaf[1:])} <= set(leaves)
    return [line for line in text.splitlines() if "custom-call(" in line
            and re.match(r"\s*%?block_attention", line)]


@pytest.mark.parametrize("cell", ["gpt2-xl.serve-chat",
                                  "olmoe-1b-7b.serve-assist"])
def test_decode_program_updates_the_cache_in_place(one_chip, cell,
                                                   monkeypatch):
    """``jit_decode`` as the engine builds it, at both serving cells' sizes
    (GPT-2 XL, 10 slots of 1,024; OLMoE, 16 slots of 4,096): the donated
    cache is the result's buffer, no second cache and no second set of
    weights among the temporaries, no copy of a layer's slice or of the
    whole cache anywhere in the program; the layer's access is the
    ``decode_attention`` kernel over the whole cache, and no fusion makes a
    layer's slice. The weights arrive as the engine holds them, rounded
    once at load, so the program converts none: GPT-2 XL's arguments are
    3.1 GB of weights and the cache, where a tick rounded 6.2 GB of float32
    and held the 3.0 GB result beside them (13.8 of 21.0 ms; my chip run,
    PR 28). And it hands back ``ids`` [slots] int32, each row's argmax,
    which the next tick reads on the chip."""
    from ray_tpu.llm.engine import engine_programs
    from ray_tpu.models import kv_cache

    # the platform here is the CPU; the described chip gets what a chip gets
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas")
    slots, cfg = {"gpt2-xl.serve-chat": (10, None),
                  "olmoe-1b-7b.serve-assist": (16, _olmoe_config())}[cell]
    cfg, args = _engine_program_args(one_chip, slots=slots, width=1, cfg=cfg)
    decode = engine_programs(cfg)[2]
    args = _decode_args(one_chip, args)
    compiled = decode.lower(*args).compile()
    cache = args[2]
    cache_bytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    header = text.split("\n", 1)[0]  # HloModule jit_decode, ..._alias={...}
    assert "jit_decode" in header
    # its first result: each row's argmax, for the tick after this one
    assert f"->(s32[{slots}]" in header
    assert mem.alias_size_in_bytes == cache_bytes
    assert header.count("-alias)") == 2
    assert mem.temp_size_in_bytes < 0.5e9
    assert _weight_converts(text, args[0]) == []
    if cell == "gpt2-xl.serve-chat":
        weights = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(args[0]))
        assert 3.1e9 < weights < 3.13e9  # 1.56 B parameters in bf16
        assert abs(mem.argument_size_in_bytes / (weights + cache_bytes) - 1
                   ) < 0.02
    assert _cache_sized(text, cache) == []
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "decode_attention" in line]
    assert len(calls) == 1  # the layer scan instantiates it once
    # told which slots decode (their indices and count), and the whole
    # cache still the first operand of its rank, where a trace's reader
    # (``benchmarks/lib/decode_attn.py``) takes the sizes from
    from benchmarks.lib import decode_attn

    assert f"s32[{slots + 1}]" in calls[0]
    assert decode_attn.cache_shape(calls[0])[:5] == cache["k"].shape
    # and no fusion makes a layer's slice: a decode step has no business to
    assert _cache_sized(text, cache, "fusion") == []


def test_prefill_program_copies_no_layer_of_the_cache(one_chip, monkeypatch):
    """``jit_prefill`` at bucket 256, B = 1. Its cache is not donated (a
    prefix-cache entry is shared), so the entry computation copies it once;
    inside the layer loop nothing cache-sized is copied or transposed: the
    chunk attends through the ``block_attention`` kernel (D = 64, 25 heads,
    one call in the layer scan) over the whole cache. It converts no weight
    either: an admission rounded the whole model too."""
    from ray_tpu.llm.engine import engine_programs
    from ray_tpu.models import kv_cache

    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas")
    cfg, args = _engine_program_args(one_chip, slots=1, width=256)
    prefill = engine_programs(cfg)[0]
    compiled = prefill.lower(*args).compile()
    text = compiled.as_text()
    assert "jit_prefill" in text.split("\n", 1)[0]
    assert _weight_converts(text, args[0]) == []
    copies = _cache_sized(text, args[2])
    assert [c for c in copies if c[0] != "ENTRY"] == []
    assert len(copies) <= 2
    assert len(_block_attention(text, args[2])) == 1


def _grouped_products(text):
    """(dtype, rows, columns, the rest of the line) of a compiled program's
    ``grouped_matmul`` kernels (``ops/grouped_matmul.py``), each checked for
    what the benchmark's readers find it by: a Mosaic custom call whose
    ``op_name`` holds the ``moe.experts`` scope."""
    import re

    kernels = []
    for line in text.splitlines():
        m = re.match(
            r"\s*%[\w.-]*grouped_matmul[\w.-]* = (\w+)\[(\d+),(\d+)\]"
            r".* custom-call\((.*)", line)
        if m is None:
            continue
        assert 'custom_call_target="tpu_custom_call"' in line
        assert "moe.experts" in re.search(
            r'op_name="([^"]*)"', line).group(1), line[-300:]
        kernels.append(m.groups())
    return kernels


# ------------------------------------------------------- OLMoE in the engine
# The second serving cell's size: OLMoE-1B-7B at depth 8, bf16 weights, 16
# slots of 4096 positions (``benchmarks/configs/olmoe-1b-7b.json``).


def _olmoe_config():
    """The program's configuration of ``olmoe-1b-7b``, as the benchmark
    builds it from the configuration file."""
    from benchmarks import run
    from benchmarks.lib import program

    return program.model_config(
        run.load_cell("olmoe-1b-7b.serve-assist")[2])


@pytest.mark.parametrize("program, slots, width", [
    ("decode", 16, 1), ("prefill", 1, 2048)])
def test_olmoe_programs_read_the_experts_where_they_lie(
        one_chip, program, slots, width, monkeypatch):
    """``jit_decode`` at 16 slots and ``jit_prefill`` at the largest bucket,
    at the published widths: they fit the chip beside each other's
    arguments; the three grouped products are ``grouped_matmul`` kernels
    (``ops/grouped_matmul.py``; the compiler's ragged-dot before PR 44),
    under the ``moe.experts`` scope a trace's reader finds them by,
    over ALL layers' experts as one operand ([8 x 64, K, N], a
    bitcast of the parameter), so no layer's 805 MB of experts is cut out
    and copied for them (19.6 of 48 ms a tick when it was; my chip run, PR
    27); no weight is converted (they are bf16 and stay so); and the cache
    is updated in place as GPT-2 XL's is."""
    import re

    from ray_tpu.llm.engine import engine_programs
    from ray_tpu.models import kv_cache

    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas")
    monkeypatch.setattr(grouped_matmul, "_impl", lambda: "pallas")
    cfg, args = _engine_program_args(one_chip, slots, width, _olmoe_config())
    assert cfg.param_dtype == jnp.bfloat16 and cfg.moe.dropless
    fn = engine_programs(cfg)[0 if program == "prefill" else 2]
    if program == "decode":
        args = _decode_args(one_chip, args)
    compiled = fn.lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    param_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(args[0]))
    assert 7.0e9 < param_bytes < 7.2e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13e9
    kernels = _grouped_products(text)
    assert len(kernels) == 3 and "ragged-dot" not in text
    rows = slots * width * cfg.moe.top_k
    for _, m, _, operands in kernels:
        assert int(m) == rows
        assert re.search(r"bf16\[512,(2048,1024|1024,2048)\]", operands)
    # nothing as large as a layer's expert matrix is sliced, copied or
    # converted anywhere in the program
    big = re.compile(
        r"= \w+\[(64|512),(2048,1024|1024,2048)\]\S* "
        r"(copy|convert|dynamic-slice|fusion)\(")
    assert [line[:160] for line in text.splitlines() if big.search(line)
            ] == []
    if program == "decode":
        cache_bytes = sum(a.size * a.dtype.itemsize
                          for a in args[2].values())
        assert mem.alias_size_in_bytes == cache_bytes
        assert _cache_sized(text, args[2]) == []


# -------------------------------------------------- Trinity-Mini in the engine
# The third serving cell's size: afmoe at depth 5, bf16 weights, 32 slots,
# one full layer of 8192 positions and four rings of 4096
# (``benchmarks/configs/trinity-mini.json``).


def _trinity_config():
    from benchmarks import run
    from benchmarks.lib import program

    return program.model_config(
        run.load_cell("trinity-mini.serve-mixed")[2])


@pytest.mark.parametrize("program, slots, width", [
    ("decode", 32, 1), ("prefill", 1, 2048)])
def test_trinity_programs_fit_the_chip_and_keep_both_caches_in_place(
        one_chip, program, slots, width, monkeypatch):
    """``jit_decode`` at 32 slots and ``jit_prefill`` at the largest bucket,
    at the published widths: the v5e compiler takes the decode kernel at
    G = 8, with the window over the rings and without it over the full
    layer, once a layer (nothing is scanned: five kinds, one of each); the
    grouped products (``grouped_matmul`` kernels under ``moe.experts``) run
    over the four routed layers' experts as one operand ([4 x 128, K, N]); a program's arguments and temporaries fit the chip;
    a prefill chunk attends through the ``block_attention`` kernel, so no
    scores are among its temporaries; the prefill's logits are the one row the
    host reads, not 2048 rows of 200192."""
    import re

    from ray_tpu.llm.engine import engine_programs
    from ray_tpu.models import kv_cache

    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas")
    monkeypatch.setattr(grouped_matmul, "_impl", lambda: "pallas")
    cfg, args = _engine_program_args(
        one_chip, slots, width, _trinity_config(), block=2048)
    assert cfg.param_dtype == jnp.bfloat16 and cfg.moe.dropless
    cache = args[2]
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, slots, 4, 128, 8192), "v": (1, slots, 4, 128, 8192),
        "k_window": (4, slots, 4, 128, 4096),
        "v_window": (4, slots, 4, 128, 4096)}
    if program == "decode":
        compiled = engine_programs(cfg)[2].lower(
            *_decode_args(one_chip, args)).compile()
    else:
        rows = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
        compiled = engine_programs(cfg, own_cache=True)[0].lower(
            *args, rows=rows).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    param_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(args[0]))
    assert 8.4e9 < param_bytes < 8.6e9      # 4.24 B parameters in bf16
    cache_bytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < (
        15.0e9 if program == "decode" else 12.0e9)
    kernels = _grouped_products(text)
    assert len(kernels) == 3 * 4            # four routed layers, unrolled
    assert "ragged-dot" not in text
    for _, m, _, operands in kernels:
        assert int(m) == slots * width * cfg.moe.top_k
        assert re.search(r"bf16\[512,(2048,1024|1024,2048)\]", operands)
    big = re.compile(
        r"= \w+\[(128|512),(2048,1024|1024,2048)\]\S* "
        r"(copy|convert|dynamic-slice|fusion)\(")
    assert [line[:160] for line in text.splitlines() if big.search(line)
            ] == []
    if program == "decode":
        assert 1.5e9 < cache_bytes < 1.7e9  # 0.54 GB full + 1.07 GB rings
        assert mem.alias_size_in_bytes == cache_bytes
        assert mem.temp_size_in_bytes < 0.5e9
        calls = [line for line in text.splitlines() if "custom-call(" in line
                 and re.match(r"\s*%?decode_attention", line)]
        assert len([c for c in calls if "decode_attention_window" in c]) == 4
        assert len(calls) == 5
        assert all(f"s32[{slots + 1}]" in c for c in calls)  # the live slots
        assert _weight_converts(text, args[0]) == []
    else:
        # a chunk attends through the block kernel, once a layer, over the
        # slot's own cache in place: 0.37 GB of temporaries (the routed
        # layers' rows), where the float32 scores, a query block at a time,
        # made them 0.41 GB and the entry copied the full layer's cache
        assert len(_block_attention(text, cache)) == 5
        assert _cache_sized(text, cache) == []
        assert mem.alias_size_in_bytes == cache_bytes
        assert mem.temp_size_in_bytes < 0.39e9
        assert "f32[1,1,200192]" in text.split("\n", 1)[0]


def _walks_in_pieces(updates, heads, head_bytes):
    """Each of a one-token state kernel's calls declares, as its
    ``vmem_limit_bytes``, the two rings of ``DEPTH`` pieces it asks for as
    scratch (``ops/ssm.py:visit_live``; a piece is at most ``PIECE_BYTES``,
    a slot whole in the three cells) and 32 MB for its small operands, and
    the compiler fitted the kernel in."""
    import re

    from ray_tpu.ops import ssm

    piece = head_bytes * ssm.heads_a_piece(heads, head_bytes)
    rings = 2 * ssm.DEPTH * piece
    assert updates and piece <= ssm.PIECE_BYTES
    for call in updates:
        declared = int(re.search(
            r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', call).group(1))
        assert rings < declared == rings + (32 << 20)


# ---------------------------------- Granite 4.0-H Micro's engine programs
# The seventh cell's size: the whole model, 48 slots
# (``benchmarks/configs/granite-4.0-h-micro.json``).


@pytest.mark.parametrize("program, slots, width", [
    ("decode", 48, 1), ("prefill", 1, 1024)])
def test_granite_programs_fit_the_chip_and_step_the_state_in_place(
        one_chip, program, slots, width, monkeypatch):
    """``jit_decode`` at 48 slots and ``jit_prefill`` at the largest bucket,
    at the published widths and depth: the v5e compiler takes the
    ``ssm_update`` kernel over the whole state (nine calls, one a state
    layer of the period, which is scanned four times) and the decode kernel
    at G = 4 and D = 64; the cache of two kinds is the program's argument
    and its result in one buffer; the convolution's rows are one flat leaf
    that no layer's write repacks; the tied table is read where it lies by
    the gather and by the head."""
    import re

    from benchmarks import run
    from benchmarks.lib import program as harness
    from ray_tpu.llm.engine import engine_programs
    from ray_tpu.models import kv_cache

    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas")
    cfg, args = _engine_program_args(
        one_chip, slots, width, harness.model_config(
            run.load_cell("granite-4.0-h-micro.serve-chat")[2]), block=1024)
    cache = args[2]
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {
        "k": ((4, slots, 8, 64, 3072), jnp.bfloat16),
        "v": ((4, slots, 8, 64, 3072), jnp.bfloat16),
        "ssm": ((36, slots, 64, 64, 128), jnp.float32),
        "conv": ((36, slots, 3 * 4352), jnp.bfloat16)}
    param_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(args[0]))
    assert 6.37e9 < param_bytes < 6.39e9    # 3.19 B parameters in bf16
    cache_bytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    if program == "decode":
        compiled = engine_programs(cfg)[2].lower(
            *_decode_args(one_chip, args)).compile()
    else:
        rows = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
        compiled = engine_programs(cfg, own_cache=True)[0].lower(
            *args, rows=rows).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    # no copy or conversion of the whole table
    assert not re.search(
        r"= \w+\[100352,2048\]\S* (copy|transpose)\(", text)
    if program == "decode":
        assert 4.8e9 < cache_bytes < 4.9e9  # 3.62 GB of state, 1.21 of KV
        assert mem.alias_size_in_bytes == cache_bytes
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11.4e9
        assert mem.temp_size_in_bytes < 0.05e9
        calls = [line for line in text.splitlines() if "custom-call(" in line]
        updates = [c for c in calls if re.match(r"\s*%?ssm_update", c)]
        assert len(updates) == 9
        assert all(f"s32[{slots + 1}]" in c
                   and f"f32[36,{slots},64,64,128]" in c for c in updates)
        _walks_in_pieces(updates, 64, 64 * 128 * 4)
        assert len([c for c in calls
                    if re.match(r"\s*%?decode_attention", c)]) == 1
        # the rows are not repacked around a layer's write
        assert "remat_compressed" not in text
        assert _weight_converts(text, args[0]) == []
    else:
        assert mem.temp_size_in_bytes < 0.5e9
        assert "f32[1,1,100352]" in text.split("\n", 1)[0]


# ------------------------------------------ Olmo-Hybrid-7B's engine programs
# The eighth cell's size: 16 of 32 layers at the published widths, 8 slots
# of 8,704 positions (``benchmarks/configs/olmo-hybrid-7b.json``).


@pytest.mark.parametrize("program, slots, width", [
    ("decode", 8, 1), ("prefill", 1, 1024)])
def test_olmo_hybrid_programs_fit_the_chip_and_step_the_state_in_place(
        one_chip, program, slots, width, monkeypatch):
    """``jit_decode`` at 8 slots and ``jit_prefill`` at the largest bucket,
    at the published widths and the cell's depth: the v5e compiler takes the
    ``delta_update`` kernel over the whole state (three calls, one a state
    layer of the period, which is scanned four times) and the decode kernel
    at 30 kv heads of 128 and 8,704 positions; the cache of two kinds is the
    program's argument and its result in one buffer, an admission's own
    slot cache too (``own_cache``: a prompt's chunks are enqueued together,
    and none then holds a second 0.57 GB); the prefill's chunked scan (the
    inverse by halves a chunk, a scan over the chunks) compiles; the table
    and the head are read where they lie."""
    import re

    from benchmarks import run
    from benchmarks.lib import program as harness
    from ray_tpu.llm.engine import engine_programs
    from ray_tpu.models import kv_cache

    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas")
    config = run.load_cell("olmo-hybrid-7b.serve-docs")[2]
    assert config["serve"]["max_batch_slots"] == 8
    cfg, args = _engine_program_args(
        one_chip, slots, width, harness.model_config(config), block=1024)
    cache = args[2]
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {
        "k": ((4, slots, 30, 128, 8704), jnp.bfloat16),
        "v": ((4, slots, 30, 128, 8704), jnp.bfloat16),
        # a head's [96, 192] with its values up to two lane tiles
        "ssm": ((12, slots, 30, 96, 256), jnp.float32),
        "conv": ((12, slots, 3 * 11520), jnp.bfloat16)}
    param_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(args[0]))
    assert 8.19e9 < param_bytes < 8.21e9    # 4.10 B parameters in bf16
    cache_bytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    if program == "decode":
        compiled = engine_programs(cfg)[2].lower(
            *_decode_args(one_chip, args)).compile()
    else:
        rows = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
        compiled = engine_programs(cfg, own_cache=True)[0].lower(
            *args, rows=rows).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    print(program, "arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes, "aliased", mem.alias_size_in_bytes,
          "cache", cache_bytes)
    # no copy or conversion of the table or of the head
    assert not re.search(
        r"= \w+\[100352,3840\]\S* (copy|transpose)\(", text)
    if program == "decode":
        # 4.28 GB of keys and values, 0.28 GB of state, 6.6 MB of rows
        assert 4.5e9 < cache_bytes < 4.6e9
        assert mem.alias_size_in_bytes >= cache_bytes
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.0e9
        assert mem.temp_size_in_bytes < 0.05e9
        calls = [line for line in text.splitlines() if "custom-call(" in line]
        updates = [c for c in calls if re.match(r"\s*%?delta_update", c)]
        assert len(updates) == 3
        assert all(f"s32[{slots + 1}]" in c
                   and f"f32[12,{slots},30,96,256]" in c for c in updates)
        _walks_in_pieces(updates, 30, 96 * 256 * 4)
        assert len([c for c in calls
                    if re.match(r"\s*%?decode_attention", c)]) == 1
        assert _weight_converts(text, args[0]) == []
    else:
        assert mem.alias_size_in_bytes >= cache_bytes
        # the four attention layers are one kind, scanned: one call
        assert len(_block_attention(text, cache)) == 1
        assert _cache_sized(text, cache) == []
        assert mem.temp_size_in_bytes < 0.39e9
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10.5e9
        assert "f32[1,1,100352]" in text.split("\n", 1)[0]


# ------------------------------------------- Ling-3.0-flash's engine programs
# The ninth cell's size: one period of six layers at the published widths,
# one chip's share of a four-way expert-parallel group, 64 slots of 19,456
# positions (``benchmarks/configs/ling-3.0-flash.json``).


@pytest.mark.parametrize("program, slots, width", [
    ("decode", 64, 1), ("prefill", 1, 2048)])
def test_ling_programs_fit_the_chip_and_keep_states_and_latents_in_place(
        one_chip, program, slots, width, monkeypatch):
    """``jit_decode`` at 64 slots and ``jit_prefill`` at the largest bucket:
    the v5e compiler takes the ``kda_update`` kernel over the whole state
    (two calls: the dense layer's, and one for the four routed KDA layers,
    which are one scan) and the ``latent_decode_attention`` kernel over one
    row of 576 values a position; the share's grouped products are the
    Pallas kernel over the stacked ``[5 x 128, ..]`` experts, and a chunk's
    rows reach their tokens through ``rows_to_tokens`` (12 rows a visit;
    a tick's 192 rows, 1.5 a visit, stay XLA's scatter-add: PR 52); the
    cache of two kinds is the program's argument and its result in one
    buffer; the prefill's chunked KDA scan and the latent blocks'
    running softmax compile with no array of the cache's length times the
    chunk's; the table and the head are read where they lie."""
    import re

    from benchmarks import run
    from benchmarks.lib import program as harness
    from ray_tpu.llm.engine import engine_programs
    from ray_tpu.models import kv_cache

    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas")
    monkeypatch.setattr(grouped_matmul, "_impl", lambda: "pallas")
    monkeypatch.setattr(rows_to_tokens, "_impl", lambda: "pallas")
    config = run.load_cell("ling-3.0-flash.serve-longgen")[2]
    assert config["serve"]["max_batch_slots"] == 64
    cfg, args = _engine_program_args(
        one_chip, slots, width, harness.model_config(config), block=2048)
    cache = args[2]
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {
        "ssm": ((5, slots, 32, 128, 128), jnp.float32),
        "conv": ((5, slots, 3 * 12288), jnp.bfloat16),
        "latent": ((1, slots, 1, 576, 19456), jnp.bfloat16)}
    param_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(args[0]))
    assert 8.70e9 < param_bytes < 8.72e9    # 4.35 B parameters in bf16
    cache_bytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    if program == "decode":
        compiled = engine_programs(cfg)[2].lower(
            *_decode_args(one_chip, args)).compile()
    else:
        rows = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
        compiled = engine_programs(cfg, own_cache=True)[0].lower(
            *args, rows=rows).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    print(program, "arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes, "aliased", mem.alias_size_in_bytes,
          "cache", cache_bytes)
    assert mem.alias_size_in_bytes >= cache_bytes
    # no copy or conversion of the table or of the head
    assert not re.search(r"= \w+\[39296,2560\]\S* (copy|transpose)\(", text)
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    # three products a routed kind (the scanned KDA layers, the latent
    # layer), in the pass that holds a uniform router's rows and in the
    # overflow passes behind it (``moe._grouped_share``)
    assert len([c for c in calls
                if re.match(r"\s*%?grouped_matmul", c)]) == 12
    # the combine of each routed kind's two branches, where the rule takes it
    assert len([c for c in calls if re.match(r"\s*%?rows_to_tokens", c)]) == (
        0 if program == "decode" else 4)
    if program == "decode":
        # 0.67 GB of states, 24 MB of rows, 1.43 GB of latents
        assert 2.12e9 < cache_bytes < 2.14e9
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10.9e9
        assert mem.temp_size_in_bytes < 0.05e9
        updates = [c for c in calls if re.match(r"\s*%?kda_update", c)]
        assert len(updates) == 2
        assert all(f"s32[{slots + 1}]" in c
                   and f"f32[5,{slots},32,128,128]" in c for c in updates)
        _walks_in_pieces(updates, 32, 128 * 128 * 4)
        latent = [c for c in calls
                  if re.match(r"\s*%?latent_decode_attention", c)]
        assert len(latent) == 1
        assert f"bf16[1,{slots},1,576,19456]" in latent[0]
        assert _weight_converts(text, args[0]) == []
        # a share's counts: experts touched and rows held, a layer
        assert "s32[6,2]" in text.split("\n", 1)[0]
    else:
        assert mem.temp_size_in_bytes < 0.40e9
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 9.2e9
        # no [heads, T, S] scores, no one-hot placement: an array with a
        # dimension of the cache's length holds no more than the leaf
        along = [dims.split(",") for dims in re.findall(
            r"\w+\[([\d,]+)\]", text) if "19456" in dims.split(",")]
        assert along and max(
            eval("*".join(dims)) for dims in along) == 576 * 19456
        assert "f32[1,1,39296]" in text.split("\n", 1)[0]


def test_ling_reference_reads_a_whole_context_beside_nothing_else(one_chip):
    """The comparison that decides ``correct`` reads a sequence that ended
    on EOS padded to the cell's ``context_limit``, one forward of the plain
    float32 reference over 19,455 tokens on the chip the engine has left
    (``runners/serve_open_loop_median.py``): weights as the program holds
    them and the temporaries fit the chip's 16 GB with room, which they did
    not while a layer's experts were a slice (a copy) of the stacked ones."""
    import functools

    from benchmarks import run
    from benchmarks.lib import program as harness, reference
    from benchmarks.runners import serve_open_loop_median as runner
    from ray_tpu.models import module_for

    _, workload, config = run.load_cell("ling-3.0-flash.serve-longgen")[:3]
    limit = int(workload["traffic"]["context_limit"])
    assert limit == 19456
    cfg = harness.model_config(config)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: module_for(cfg).init_params(
            cfg, jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct((1, limit), jnp.int32, sharding=one_chip)
    with jax.default_matmul_precision("highest"):
        mem = jax.jit(functools.partial(
            runner.greedy_gaps, reference.logits_of(config))).lower(
                params, tokens).compile().memory_analysis()
    print("arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes)
    assert 8.70e9 < mem.argument_size_in_bytes < 8.72e9
    # 3.27 GB: the logits [19455, 39296] float32 are 3.06 of them
    assert mem.temp_size_in_bytes < 4.0e9


# ------------------------------------------ DeepSeek-V3.2-Exp's engine programs
# The eleventh cell's size: a dense and four routed layers at the published
# widths, one chip's share of a sixteen-way expert-parallel group, 8 slots of
# 33,280 positions (``benchmarks/configs/deepseek-v3.2-exp.json``).


@pytest.mark.parametrize("program, slots, width", [
    ("decode", 8, 1), ("prefill", 1, 2048)])
def test_deepseek_v32_programs_fit_the_chip_and_keep_both_rows_in_place(
        one_chip, program, slots, width, monkeypatch):
    """``jit_decode`` at 8 slots and ``jit_prefill`` at the largest bucket
    (the longest chunk: 2,048 tokens against a cache of 33,280): the v5e
    compiler takes the ``latent_decode_attention`` kernel with the choice's
    mask beside the cache (two calls: the dense layer's, and one for the
    four routed layers, which are one scan) and the
    ``selected_block_attention`` kernel at 128 heads of 2,048 queries (a
    call a kind of layer and width of the cache it may choose over); the
    cache of two leaves, the latent rows position-minor and the indexer's
    keys position-major, is the program's argument and its result in one
    buffer; no array a head wide has the cache's length (the index scores
    and their choice are [2048, 33280]); bytes of arguments and temporaries
    are printed. The indexer's two steps are the ``index_scores`` and
    ``index_kth_largest`` kernels (PR 58), a pair a kind of layer in a tick
    and a pair a kind and width in a chunk, each reading the ``index`` leaf
    where it lies: nothing a head wide leaves VMEM (no [8, 64, 33280]
    products and no [8, 15, 33280] compares in ``jit_decode``, no
    [2048, 64, n] float32 in ``jit_prefill``)."""
    import re

    from benchmarks import run
    from benchmarks.lib import program as harness
    from ray_tpu.llm.engine import engine_programs
    from ray_tpu.models import kv_cache

    from ray_tpu.ops import index_select

    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas")
    monkeypatch.setattr(grouped_matmul, "_impl", lambda: "pallas")
    monkeypatch.setattr(rows_to_tokens, "_impl", lambda: "pallas")
    config = run.load_cell("deepseek-v3.2-exp.serve-longdoc")[2]
    assert config["serve"]["max_batch_slots"] == 8
    cfg, args = _engine_program_args(
        one_chip, slots, width, harness.model_config(config), block=2048)
    S = cfg.max_seq_len
    cache = args[2]
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {
        "latent": ((5, slots, 1, 576, S), jnp.bfloat16),
        "index": ((5, slots, S, 128), jnp.bfloat16)}
    params = sum(a.size for a in jax.tree.leaves(args[0]))
    assert params == 4_635_518_208
    cache_bytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    assert cache_bytes == slots * S * 5 * (576 + 128) * 2
    if program == "decode":
        compiled = engine_programs(cfg)[2].lower(
            *_decode_args(one_chip, args)).compile()
    else:
        rows = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
        compiled = engine_programs(cfg, own_cache=True)[0].lower(
            *args, rows=rows).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    print(program, "arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes, "aliased", mem.alias_size_in_bytes,
          "cache", cache_bytes)
    assert mem.alias_size_in_bytes >= cache_bytes
    assert not re.search(r"= \w+\[16160,7168\]\S* (copy|transpose)\(", text)
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    if program == "decode":
        # 9.27 GB of weights and 1.87 GB of cache: 70% of the chip
        assert 11.1e9 < mem.argument_size_in_bytes < 11.2e9
        assert mem.temp_size_in_bytes < 0.05e9
        latent = [c for c in calls
                  if re.match(r"\s*%?latent_decode_attention", c)]
        assert len(latent) == 2
        # the choice rides beside the cache: [slots, 1, S] float32
        assert all(f"bf16[5,{slots},1,576,{S}]" in c
                   and f"f32[{slots},1,{S}]" in c for c in latent)
        # no copy of either leaf, whole or a layer's
        assert not re.search(
            rf"= bf16\[(5,)?{slots},(1,576,{S}|{S},128)\]\S* (copy|transpose)\(",
            text)
        assert _weight_converts(text, args[0]) == []
        assert "s32[5,2]" in text.split("\n", 1)[0]
        # the indexer's kernels, once a kind of layer: the scores read the
        # leaf itself, the search the [slots, S] scores
        scored = [c for c in calls if re.match(r"\s*%?index_scores", c)]
        searched = [c for c in calls
                    if re.match(r"\s*%?index_kth_largest", c)]
        assert len(scored) == len(searched) == 2
        assert all(f"bf16[5,{slots},{S},128]" in c
                   and f"f32[{slots},1,{S}]" in c for c in scored)
        assert all(f"f32[{slots},{S}]" in c for c in searched)
        # nothing a head wide along the cache, product or compare
        assert not re.search(rf"\[{slots},(64|15),{S}\]", text)
    else:
        assert mem.temp_size_in_bytes < 2.0e9
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11.5e9
        chosen = [c for c in calls
                  if re.match(r"\s*%?selected_block_attention", c)]
        # two kinds of layer x the widths a chunk may choose over
        assert len(chosen) == 2 * (len(kv_cache.CHOICE_WIDTHS) + 1)
        # the queries whole (128 + 64 channels: a score is one product), a
        # head's [Wuk | Wuv], 128 values a head out; the choice comes as wide
        # as it was made
        assert all("bf16[128,2048,192]" in c and "bf16[128,256,512]" in c
                   and "bf16[128,2048,128]" in c for c in chosen)
        widths = (*kv_cache.CHOICE_WIDTHS, S)
        assert sorted(w for c in chosen for w in widths
                      if f"s8[2048,{w}]" in c) == sorted(2 * widths)
        # nothing a head wide along the cache: no [heads, tokens, S] score
        assert not re.search(rf"\[(128|64),2048,{S}\]|\[2048,(128|64),{S}\]",
                             text)
        # the indexer's kernels, a pair a kind of layer and width: a tile of
        # queries against the leaf where it lies, [2048, width] scores out
        scored = [c for c in calls if re.match(r"\s*%?index_scores", c)]
        searched = [c for c in calls
                    if re.match(r"\s*%?index_kth_largest", c)]
        assert len(scored) == len(searched) == 2 * len(widths)
        assert all(f"bf16[5,1,{S},128]" in c for c in scored)
        tile = index_select.QUERIES
        for calls_of, shape in ((scored, f"f32[{2048 // tile},{tile},{{}}]"),
                                (searched, "f32[2048,{}]")):
            assert sorted(w for c in calls_of for w in widths
                          if shape.format(w) in c) == sorted(2 * widths)
        # and the products of a block before the sum over heads stay in VMEM
        assert not re.search(r"f32\[2048,64,\d+\]", text)
        # neither leaf copied, whole or a layer's
        assert not re.search(
            rf"= bf16\[(5,)?1,(1,576,{S}|{S},128)\]\S* (copy|transpose)\(",
            text)


def test_deepseek_v32_reference_reads_a_whole_context_beside_nothing_else(
        one_chip):
    """The comparison that decides ``correct`` reads the check requests
    padded to 24,576 positions, and a sequence that ended on EOS padded to
    the cell's ``context_limit``, in one forward of the plain float32
    reference on the chip the engine has left: weights as the program holds
    them and the temporaries fit the chip's 16 GB with room."""
    import functools

    from benchmarks import run
    from benchmarks.lib import program as harness, reference
    from benchmarks.runners import serve_open_loop_median as runner
    from ray_tpu.models import module_for

    _, workload, config = run.load_cell("deepseek-v3.2-exp.serve-longdoc")[:3]
    limit = int(workload["traffic"]["context_limit"])
    cfg = harness.model_config(config)
    assert limit == cfg.max_seq_len
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: module_for(cfg).init_params(
            cfg, jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct((1, limit), jnp.int32, sharding=one_chip)
    with jax.default_matmul_precision("highest"):
        mem = jax.jit(functools.partial(
            runner.greedy_gaps, reference.logits_of(config))).lower(
                params, tokens).compile().memory_analysis()
    print("arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes)
    assert 9.27e9 < mem.argument_size_in_bytes < 9.28e9
    # 4.34 GB at 33,280 positions: the logits [33279, 16160] float32 are
    # 2.15 of them, the chosen set [33280, 33280] booleans 1.1
    assert mem.temp_size_in_bytes < 5.0e9


# -------------------------------------------- SmallThinker's training step
# The sixth cell's size: one chip's share of a four-way expert-parallel
# layer, batch 2 x 8192 (``benchmarks/configs/smallthinker-21b-a3b.json``).


@pytest.mark.parametrize("window, fwd, bwd", [
    (4096, "flash_window_fwd", "flash_window_bwd"),
    (None, "flash_fwd", "flash_bwd")], ids=["window", "full"])
def test_flash_with_a_window_and_grouped_heads_compiles_for_v5e(
        one_chip, window, fwd, bwd):
    """28 query heads over 4 kv heads of 128 at 8192 tokens, forward and
    backward: whole k and v (forward) and whole q, dO and dq (backward) of a
    head are 2 MB each in VMEM, past the compiler's own 16 MiB limit, which
    the calls raise for themselves; and each kind of call carries its name
    (``benchmarks/lib/train_moe.py`` costs a call by it)."""
    q = jax.ShapeDtypeStruct((2, 28, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((2, 4, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, True, None, None, False, window)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(q, k, k).compile().as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line]
    assert len(calls) == 2
    assert sum(fwd in c for c in calls) == sum(bwd in c for c in calls) == 1
    # k and v go in with the kv heads they have: nothing 28 heads wide but
    # q, o, their gradients and each query head's own dk and dv
    assert "bf16[2,28,8192,128]" in text and "bf16[8,8192,128]" in text


# (R, G, T, dtype, buffers) at the edges of ``rows_to_tokens.engages`` for
# rows of 2,560: the most tokens SMEM is asked to hold; with them the most
# visits the rule admits (R / ROWS_A_VISIT: 8,320 bounds beside the tokens);
# the most VMEM, one float32 buffer (77.9 MB) and two bfloat16 ones (80.6
# of the 83.9 allowed); the most groups VMEM admits beside one tile
ROWS_TO_TOKENS_EDGES = {
    "most-rows": (65536, 16, 32768, jnp.float32, 1),
    "most-visits": (65536, 128, 32768, jnp.float32, 1),
    "most-vmem": (65536, 16, 12288, jnp.float32, 1),
    "most-vmem-two-bfloat16": (65536, 16, 12288, jnp.bfloat16, 2),
    "most-groups": (1600, 200, 512, jnp.float32, 1),
}


@pytest.mark.parametrize("edge", list(ROWS_TO_TOKENS_EDGES))
def test_rows_to_tokens_compiles_at_the_edges_of_its_rule(
        one_chip, edge, monkeypatch):
    """What ``engages`` admits compiles: the rule's limits (tokens and
    bounds in SMEM, buffers in VMEM) are ones the chip's compiler took, so
    a call the rule hands the kernel is never a compile error (a shape past
    them is XLA's scatter-add, as off the chip)."""
    R, G, T, dtype, buffers = ROWS_TO_TOKENS_EDGES[edge]
    monkeypatch.setattr(rows_to_tokens, "_impl", lambda: "pallas")
    assert rows_to_tokens.engages(R, G, T, 2560, dtype, buffers)
    rows = jax.ShapeDtypeStruct((R, 2560), dtype, sharding=one_chip)
    token = jax.ShapeDtypeStruct((R,), jnp.int32, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((G,), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda rows, token, sizes: rows_to_tokens.rows_to_tokens(
        (rows,) * buffers, token, sizes, T)).lower(
            rows, token, sizes).compile().as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line]
    assert len(calls) == 1 and "rows_to_tokens" in calls[0]


def _loss_head(text, mem, vocab, embed, was):
    """The compiled step's loss head (``ops/xent.py``, PR 43): the chunk's
    logits are projected once, in the forward scan, where dW is made too;
    no remat of the loss; the backward's scaling of dW by the constant
    cotangent 1 is folded (no multiply over [V, E] outside the optimizer's);
    arguments + temporaries within 100 MB of ``was``, the figure with the
    loss body under ``jax.checkpoint``."""
    import re

    def products(what):
        return [line for line in text.splitlines()
                if " convolution(" in line and f"closed_call/{what}/" in line]

    logits, dw = products("bce,ve->bcv"), products("bcv,bce->ve")
    assert len(logits) == 1 and f",{vocab}]" in logits[0].split(" = ")[1]
    assert len(dw) == 1 and f"[{vocab},{embed}," in dw[0].split(" = ")[1]
    assert "transpose(jvp" not in logits[0] + dw[0]
    assert "rematted_computation/bce,ve->bcv" not in text
    assert not re.search(
        rf"\[{vocab},{embed}\]\S* multiply\([^\n]*op_name=\"[^\"]*transpose\(",
        text)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            <= was + 100_000_000)


def _compiled_train_step(one_chip, cell_name, monkeypatch):
    """(model configuration, compiled step) of a training cell for the
    described chip: the cell's own widths and batch, the flash kernels, the
    grouped products' and the one that adds rows to tokens (the platform's
    choices, made here for it)."""
    import dataclasses

    monkeypatch.setattr(grouped_matmul, "_impl", lambda: "pallas")
    monkeypatch.setattr(rows_to_tokens, "_impl", lambda: "pallas")

    from benchmarks import run
    from benchmarks.lib import program
    from ray_tpu.models import config_for
    from ray_tpu.train.step import (
        OptimizerConfig, create_train_state, make_train_step)

    _, cell, config, _, _ = run.load_cell(cell_name)
    model = program.trainer_model(config)
    cfg = dataclasses.replace(
        config_for(model.pop("family"), **model), attention_impl="flash")
    opt = OptimizerConfig().build()
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(
            lambda: create_train_state(cfg, opt, jax.random.PRNGKey(0))))
    job = cell["job"]
    batch = {"tokens": jax.ShapeDtypeStruct(
        (job["batch_size"], job["seq_len"] + 1), jnp.int32,
        sharding=one_chip)}
    return cfg, make_train_step(cfg, opt).lower(state, batch).compile()


def test_gpt2_medium_step_projects_its_logits_once(one_chip, monkeypatch):
    """``gpt2-medium.train-steady``'s step at its real widths, batch 16 x
    1024, remat ``dots``: the tied head's loss is three products a chunk
    and holds what it held (4.26 GB of state in, 13.58 GB of temporaries:
    17,837,841,408 before, 17,837,905,920 after; sandbox compile, PR 43)."""
    cfg, compiled = _compiled_train_step(
        one_chip, "gpt2-medium.train-steady", monkeypatch)
    _loss_head(compiled.as_text(), compiled.memory_analysis(),
               cfg.vocab_size, cfg.embed_dim, was=17_837_841_408)


def test_smallthinker_step_fits_the_chip_with_its_window_in_the_kernels(
        one_chip, monkeypatch):
    """The cell's train step for the described chip: accepted at batch 2 x
    8192 with remat ``dots`` (7.9 GB of state in, 10.2 GB of temporaries),
    four flash calls forward and four backward (one full, three windowed
    each), the grouped products as this repo's Pallas kernels in both
    directions (``grouped_matmul``, and ``grouped_matmul_dw`` for the
    weights' gradients: three of a layer's six transposes; every call under
    the ``moe.experts`` scope, the ``custom_vjp``'s backward too), the
    two up-projections kept for the backward pass and not run again, the
    down product not run again either and its cotangent's rows gathered in
    bfloat16 (PR 49: the gate rides the hidden row), the rows added to
    their tokens by the ``rows_to_tokens`` kernel in the combine and in the
    dispatch's backward pass, with no scatter-add of [.., 2560] rows and no
    select over the row buffer left in a routed layer (PR 52), no count
    made by a scatter-add of ones, no [B, H, T, T] array anywhere, and a
    loss head that projects a chunk's logits once (``_loss_head``)."""
    import re

    cfg, compiled = _compiled_train_step(
        one_chip, "smallthinker-21b-a3b.train-seq8k", monkeypatch)
    assert cfg.moe.dropless and cfg.moe.num_held == 16
    mem = compiled.memory_analysis()
    assert 7.8e9 < mem.argument_size_in_bytes < 7.95e9  # 656.6M x 12 bytes
    # 10,170,040,832 (sandbox compile, PR 41); 9,356,529,152 before the
    # up-projections were residuals: the compiler's sum moves by 0.81 GB,
    # the chip's peak by 0.13 (14.880 -> 15.007 GB, ``memory_peak_bytes``);
    # 9,126,115,328 -> 8,358,041,600 when the backward pass stopped making
    # float32 [36864, 2560] rows (sandbox compile, PR 49); 8,183,665,152 when the
    # selects over the row buffer left with the scatter-adds (sandbox
    # compile, PR 52)
    assert mem.temp_size_in_bytes < 8.25e9
    text = compiled.as_text()
    # 18,048,474,112 with the loss body under remat (sandbox compile, PR 42's
    # tree); 18,048,409,600 with the gradients made in the loss's forward
    _loss_head(text, mem, cfg.vocab_size, cfg.embed_dim, was=18_048_474_112)
    names = [line.split(" = ")[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line]
    flash = [n for n in names if "flash_" in n]
    assert sum("window_fwd" in n for n in flash) == 3
    assert sum("window_bwd" in n for n in flash) == 3
    assert len(flash) == 8
    # a layer: 9 in the branch every balanced routing takes (3 forward, 6
    # transposes; 10 with the down product again under remat, for the gates'
    # gradient, as before PR 49; 12 with the two up-projections run again,
    # as before PR 41) and 11 in the branch of further passes, which keeps
    # nothing and runs the two up-projections again
    products = [line for line in text.splitlines()
                if "tpu_custom_call" in line and " = " in line
                and "grouped_matmul" in line.split(" = ")[0]]
    assert len(products) == (9 + 11) * 4 and "ragged-dot" not in text
    # of a branch's six transposes three are the weights' gradients
    assert sum("grouped_matmul_dw" in line.split(" = ")[0]
               for line in products) == (3 + 3) * 4
    for line in products:
        assert "moe.experts" in re.search(
            r'op_name="([^"]*)"', line).group(1), line[-300:]
    # the down product -> f32[rows, D]: forward, once a branch, and in
    # nothing a checkpoint runs again
    R = moe.held_rows_bound(16384, cfg.moe)
    down = [line for line in products if f" = f32[{R},2560]" in line]
    assert R == 36864 and len(down) == 2 * 4
    assert not [line for line in down if "rematted_computation" in line]
    # rows reach their tokens through the kernel: a layer's combine
    # (float32 rows) and its dispatch's backward pass (the cotangent's, in
    # bfloat16), once in each of the layer's two branches: 8 of the 16 run
    # in a step whose routing one pass holds
    added = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line
             and "rows_to_tokens" in line.split(" = ")[0]]
    scopes = [re.search(r'op_name="([^"]*)"', line).group(1)
              for line in added]
    assert len(added) == (1 + 1) * 2 * 4
    assert sum(" = f32[16384,2560]" in line and "moe.combine" in scope
               and "transpose" not in scope
               for line, scope in zip(added, scopes)) == 2 * 4
    assert sum(" = bf16[16384,2560]" in line and "moe.dispatch" in scope
               and "transpose" in scope
               for line, scope in zip(added, scopes)) == 2 * 4
    assert not [scope for scope in scopes if "moe.experts" in scope]
    # the one scatter of [.., 2560] rows left is the embedding's gradient,
    # and no select over the [36864, 2560] buffer stands in a routed layer
    scatters = re.findall(r"= \w+\[\d+,2560\]\S* scatter\([^\n]*", text)
    assert len(scatters) == 1 and "moe." not in scatters[0]
    assert not re.search(
        rf"= \w+\[{R},2560\]\S* select\([^\n]*moe\.", text)
    # the rows of the combine's cotangent are gathered as they arrive, in
    # bfloat16: 4 a branch under ``moe.combine``, where autodiff's were
    # float32
    gathers = re.findall(
        rf"= (\w+)\[{R},2560\]\S* gather\([^\n]*"
        r'op_name="[^"]*transpose[^"]*moe\.combine/[^"]*"', text)
    assert gathers == ["bf16"] * 8
    # the counts of rows an expert are compares and column sums
    assert not re.search(r"= s32\[(16|64)\]\S* scatter\(", text)
    assert not re.search(r"\[\d+,28,8192,8192\]", text)


# -------------------------------------------- JoyAI-LLM-Flash's training step
# The tenth cell's size: one chip's share of a sixteen-way expert-parallel
# layer, batch 2 x 8192 (``benchmarks/configs/joyai-llm-flash.json``).


def _pallas_calls(text):
    """The instruction of each Pallas call in a compiled program, as a
    trace's reader sees it (``name = (results) custom-call(operands)``)."""
    return [line for line in text.splitlines()
            if "tpu_custom_call" in line and " = " in line]


@pytest.mark.parametrize("shared", [False, True],
                         ids=["keys-whole", "shared-key-an-operand"])
def test_flash_with_keys_wider_than_values_compiles_for_v5e(one_chip, shared):
    """32 heads at 8192 tokens, keys 192 and values 128 wide in one call,
    forward and backward: 192 is one and a half lane tiles, which the
    compiler takes as a block's whole last dimension; nothing is padded to
    256 in HBM; and the calls carry the name a trace's reader knows them by
    (``benchmarks/lib/train_mla.py``), whose first results it checks: o
    [64, 8192, 128] forward, dq [64, 8192, 192] backward, by its own
    ``FIRST_RESULT``. With the rotated key as an operand of its own
    ([2, 8192, 64], a fourth operand that every head of a row reads), k and
    dk are the 128 channels a head has of its own and the shared key's
    gradient is a fourth result, summed over the heads inside the call."""
    from benchmarks.lib import train_mla

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    q, v = S(2, 32, 8192, 192), S(2, 32, 8192, 128)
    if shared:
        def grads(q, k, v, shared):
            return jax.grad(
                lambda *a: flash_attention(
                    *a[:3], True, None, None, False, None, a[3])
                .astype(jnp.float32).sum(), argnums=(0, 1, 2, 3))(
                    q, k, v, shared)
        lowered = jax.jit(grads).lower(q, v, v, S(2, 8192, 64))
    else:
        lowered = jax.jit(_flash_grads).lower(q, q, v)
    text = lowered.compile().as_text()
    calls = _pallas_calls(text)
    assert len(calls) == 2
    (fwd,), (bwd,) = ([call for call in calls if kind in call.split(" = ")[0]]
                      for kind in ("flash_mla_fwd", "flash_mla_bwd"))
    assert train_mla.FIRST_RESULT.search(fwd).group(1) == "64,8192,128"
    assert train_mla.FIRST_RESULT.search(bwd).group(1) == "64,8192,192"
    results, operands = bwd.split(" = ")[1].split(" custom-call(")
    dk = "128" if shared else "192"
    assert results.startswith(
        "(bf16[64,8192,192]{2,1,0:T(8,128)(2,1)}, bf16[64,8192," + dk
        + "]{2,1,0:T(8,128)(2,1)}, bf16[64,8192,128]")
    if shared:   # its gradient a result, itself an operand
        assert "bf16[2,8192,64]" in results
        assert "bf16[2,8192,64]{2,1,0}" in operands
    assert ",256]" not in text


# --------------------------------- what crosses the flash kernels' boundary
# A projection's product writes, and the weights' gradient reads, the
# kernels' own [B x H, T, D]: no whole-array copy stands between them (PR
# 56). XLA lays a product's result out by its own rule, so this holds only
# as long as ``ops/attention.py`` says the order as a layout constraint and
# names a heads-major projection's weights first: a later change that
# brings a copy back fails here, on the CPU.


def _mixer_gradient(one_chip, cell_name):
    """The compiled gradient of ONE mixer of a training cell's model (the
    last layer's attention between the family's two pieces, under the
    step's remat) with respect to its input and its weights, at the cell's
    batch: text of the program for the described chip."""
    import dataclasses

    from benchmarks import run
    from benchmarks.lib import program
    from ray_tpu.models import config_for, decoder, module_for

    _, cell, config, _, _ = run.load_cell(cell_name)
    model = program.trainer_model(config)
    cfg = dataclasses.replace(
        config_for(model.pop("family"), **model), attention_impl="flash")
    family = module_for(cfg)
    params = jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    segment = family.layers(cfg, params["blocks"], cached=False)[0][-1]
    kind = segment.kinds[-1]
    layer = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype,
                                       sharding=one_chip),
        segment.params[-1])
    B, T = cell["job"]["batch_size"], cell["job"]["seq_len"]
    x = jax.ShapeDtypeStruct((B, T, cfg.embed_dim), cfg.dtype,
                             sharding=one_chip)

    def loss(x, layer):
        pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        mixer = jax.checkpoint(
            functools.partial(decoder._mixer, cfg, None, pos, kind),
            policy=decoder._remat_policy(cfg))
        return mixer(layer, x).astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, layer).compile().as_text()


def _whole_array_copies(text, floor=30e6):
    """The ``copy`` / ``transpose`` instructions of ``floor`` bytes or more
    in a compiled program's entry computation, fusions of that name too:
    [(name, shape with its layout)]."""
    import re

    sizes = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    lines = text.splitlines()
    entry = next(i for i, line in enumerate(lines) if line.startswith("ENTRY"))
    found = []
    for line in lines[entry:]:
        m = re.match(r"\s*(?:ROOT )?(\S+) = (\w+)\[([\d,]*)\](\{[^}]*\}) "
                     r"([\w-]+)\(", line)
        if m is None:
            continue
        name, dtype, dims, layout, op = m.groups()
        moved = op in ("copy", "transpose") or (
            op == "fusion" and ("copy" in name or "transpose" in name))
        size = sizes.get(dtype, 4)
        for d in filter(None, dims.split(",")):
            size *= int(d)
        if moved and size >= floor:
            found.append((name, f"{dtype}[{dims}]{layout}"))
    return found


@pytest.mark.parametrize("cell, left", [
    # 15 before (2.2 GB a mixer: q, k, v and o into the kernels' order,
    # forward and again under remat, the keys' concatenation, dO in and dq,
    # dk, dv out, the rotation's pair views); sandbox compile, PR 56
    ("joyai-llm-flash.train-seq8k", 0),
    # 5 before: q, o, dO and dq at 28 heads (k and v at 4 are under 30 MB)
    ("smallthinker-21b-a3b.train-seq8k", 0),
    # 11 before and 11 now: at 64 channels a head the kernels' order fills
    # half of each row's lanes, so ``_folded`` asks for it under 128 channels
    # of no product (a residual kept in that order is padded to twice its
    # size: the whole step was refused by 1.64 GB with it), the compiler
    # lays its products out positions-minor and copies each array into and
    # out of the calls, as it did. Held so that it does not grow.
    ("gpt2-medium.train-steady", 11),
])
def test_no_whole_array_copy_stands_around_the_flash_calls(one_chip, cell,
                                                           left):
    copies = _whole_array_copies(_mixer_gradient(one_chip, cell))
    assert len(copies) <= left, copies


def test_joyai_step_fits_the_chip_with_latent_attention_in_the_kernels(
        one_chip, monkeypatch):
    """The cell's train step for the described chip: accepted at batch 2 x
    8192 with remat ``dots`` beside 680.4M parameters' state (8,165,367,296
    bytes of arguments + 10,339,678,208 of temporaries; sandbox compile, PR
    55: before a share's overflow passes kept their residuals once, the
    scan stacked eleven copies of the tokens and of the share's matrices
    and the compiler refused it by 1.42 GB), six flash calls at two widths
    forward and six backward (five trunk mixers and the prediction layer's)
    and no [T, T] array anywhere, the prediction layer's operations under
    its scopes in the backward pass too, and two loss heads over the one
    table (the main one and the prediction layer's), each projecting a
    chunk's logits once."""
    import re

    cfg, compiled = _compiled_train_step(
        one_chip, "joyai-llm-flash.train-seq8k", monkeypatch)
    assert cfg.moe.num_held == 16 and cfg.moe.num_experts == 256
    assert cfg.moe.aux_loss_weight == 0.0 and cfg.moe.bias_update_rate == 0.001
    mem = compiled.memory_analysis()
    assert 8.1e9 < mem.argument_size_in_bytes < 8.2e9   # 680.4M x 12 bytes
    assert mem.temp_size_in_bytes < 10.45e9
    text = compiled.as_text()
    names = [line.split(" = ")[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line]
    flash = [n for n in names if "flash_" in n]
    assert sum("flash_mla_fwd" in n for n in flash) == 6
    assert sum("flash_mla_bwd" in n for n in flash) == 6
    assert len(flash) == 12
    assert not re.search(r"\[[\d,]*8192,8192[\d,]*\]", text)
    assert "transpose(jvp(mtp.block))/jvp(mtp.block)/checkpoint/flash_mla_bwd" \
        in text
    for scope in ("mla.q", "mla.down", "mla.up", "mla.out", "mtp.in",
                  "mtp.head"):
        assert scope in text, scope
    logits = [line for line in text.splitlines()
              if " convolution(" in line and "closed_call/bce,ve->bcv/" in line]
    assert len(logits) == 2 and all(
        f",{cfg.vocab_size}]" in line.split(" = ")[1] for line in logits)
    assert "rematted_computation/bce,ve->bcv" not in text
    assert "ragged-dot" not in text
