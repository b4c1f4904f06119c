"""GPT-2 model + SPMD train step tests on the 8-device CPU mesh."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import gpt2
from ray_tpu.parallel.mesh import MeshConfig
from ray_tpu.parallel.moe import MoEConfig, init_moe_params, moe_layer
from ray_tpu.train.step import OptimizerConfig, create_train_state, make_train_step

CFG = gpt2.GPT2_TINY


def _batch(B=4, T=64, seed=0, vocab=CFG.vocab_size):
    rng = np.random.RandomState(seed)
    return {"tokens": jnp.asarray(rng.randint(0, vocab, (B, T + 1)))}


def test_forward_shapes():
    params = gpt2.init_params(CFG, jax.random.PRNGKey(0))
    tokens = _batch()["tokens"][:, :-1]
    logits, aux = gpt2.forward(params, tokens, CFG)
    assert logits.shape == (4, 64, CFG.vocab_size)
    assert float(aux) == 0.0


def test_loss_decreases_single_device():
    opt = OptimizerConfig(learning_rate=1e-2, warmup_steps=1, total_steps=50).build()
    state = create_train_state(CFG, opt, jax.random.PRNGKey(0))
    step = make_train_step(CFG, opt)
    batch = _batch()
    first = None
    for i in range(10):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first, (first, float(metrics["loss"]))


@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(data=8),                      # pure DP
    MeshConfig(data=2, fsdp=2, tensor=2),    # DP x FSDP x TP
    MeshConfig(data=1, fsdp=4, tensor=2),    # ZeRO x TP
])
def test_spmd_train_step(mesh_cfg):
    mesh = mesh_cfg.build()
    opt = OptimizerConfig(learning_rate=1e-3).build()
    state = create_train_state(CFG, opt, jax.random.PRNGKey(0), mesh)
    step = make_train_step(CFG, opt, mesh)
    batch = _batch(B=8)
    batch = jax.device_put(
        batch, {"tokens": NamedSharding(mesh, P(("data", "fsdp"), None))}
    )
    state, m1 = step(state, batch)
    state, m2 = step(state, batch)
    assert np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) < float(m1["loss"]) + 1.0


def test_spmd_matches_single_device():
    """Sharded and unsharded training must produce the same losses."""
    opt = OptimizerConfig(learning_rate=1e-3).build()
    batch = _batch(B=8)

    state1 = create_train_state(CFG, opt, jax.random.PRNGKey(0))
    step1 = make_train_step(CFG, opt, donate=False)
    losses1 = []
    for _ in range(3):
        state1, m = step1(state1, batch)
        losses1.append(float(m["loss"]))

    mesh = MeshConfig(data=2, fsdp=2, tensor=2).build()
    state2 = create_train_state(CFG, opt, jax.random.PRNGKey(0), mesh)
    step2 = make_train_step(CFG, opt, mesh, donate=False)
    losses2 = []
    for _ in range(3):
        state2, m = step2(state2, batch)
        losses2.append(float(m["loss"]))
    np.testing.assert_allclose(losses1, losses2, rtol=2e-3)


def test_seq_parallel_ring_model():
    mesh = MeshConfig(data=2, seq=4).build()
    cfg = gpt2.GPT2Config(
        vocab_size=512, max_seq_len=128, num_layers=2, num_heads=2,
        embed_dim=64, attention_impl="ring", dtype=jnp.float32,
    )
    params = gpt2.init_params(cfg, jax.random.PRNGKey(1))
    tokens = _batch(B=4, T=64, vocab=512)["tokens"][:, :-1]
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("data", "seq")))
    logits, _ = jax.jit(
        lambda p, t: gpt2.forward(p, t, cfg, mesh)
    )(params, tokens)
    # must match the dense path
    cfg_dense = gpt2.GPT2Config(
        vocab_size=512, max_seq_len=128, num_layers=2, num_heads=2,
        embed_dim=64, attention_impl="xla", dtype=jnp.float32,
    )
    ref, _ = gpt2.forward(params, jax.device_put(tokens), cfg_dense)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref), atol=3e-4, rtol=3e-4
    )


def test_pipeline_forward_matches_sequential():
    mesh = MeshConfig(data=2, stage=4).build()
    cfg = gpt2.GPT2Config(
        vocab_size=512, max_seq_len=128, num_layers=4, num_heads=2,
        embed_dim=64, attention_impl="xla", dtype=jnp.float32, remat=False,
    )
    params = gpt2.init_params(cfg, jax.random.PRNGKey(2))
    tokens = _batch(B=8, T=32, vocab=512)["tokens"][:, :-1]
    ref, _ = gpt2.forward(params, tokens, cfg)
    out, _ = jax.jit(
        lambda p, t: gpt2.forward_pipelined(p, t, cfg, mesh, num_microbatches=4)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )


@pytest.mark.parametrize("axes", [
    {"data": 2, "stage": 2},
    {"data": 1, "stage": 4},
    {"data": 2, "stage": 2, "tensor": 2},
], ids=lambda a: "x".join(f"{k}{v}" for k, v in a.items()))
def test_pipeline_with_the_flash_kernel(axes):
    """What ``auto`` resolves to on a TPU: the dispatcher's shard_map nested
    inside ``pipeline_apply``'s, which already holds ``stage``. Forward and
    the whole pipelined train step against XLA attention."""
    n = math.prod(axes.values())
    mesh = MeshConfig(**axes).build(jax.devices()[:n])
    kw = dict(
        vocab_size=512, max_seq_len=128, num_layers=4, num_heads=2,
        embed_dim=64, dtype=jnp.float32, remat=False,
    )
    flash = gpt2.GPT2Config(attention_impl="flash_interpret", **kw)
    dense = gpt2.GPT2Config(attention_impl="xla", **kw)
    params = gpt2.init_params(flash, jax.random.PRNGKey(2))
    batch = _batch(B=8, T=128, vocab=512)
    tokens = batch["tokens"][:, :-1]
    ref, _ = gpt2.forward(params, tokens, dense)
    out, _ = jax.jit(
        lambda p, t: gpt2.forward_pipelined(p, t, flash, mesh,
                                            num_microbatches=4)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )
    opt = OptimizerConfig().build()
    losses = {}
    for cfg in (flash, dense):
        state = create_train_state(cfg, opt, jax.random.PRNGKey(0), mesh)
        step = make_train_step(cfg, opt, mesh, pipeline_microbatches=4)
        for _ in range(2):  # the second loss has been through the backward
            state, m = step(state, batch)
        losses[cfg.attention_impl] = float(m["loss"])
    assert abs(losses["flash_interpret"] - losses["xla"]) < 1e-4, losses


def test_moe_layer_routing():
    cfg = MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0)
    params = init_moe_params(jax.random.PRNGKey(0), 32, 64, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    out, aux = moe_layer(params, x, cfg)
    assert out.shape == x.shape
    assert float(aux) > 0


def test_moe_model_ep_sharded():
    mesh = MeshConfig(data=2, expert=4).build()
    cfg = gpt2.GPT2Config(
        vocab_size=512, max_seq_len=128, num_layers=2, num_heads=2,
        embed_dim=64, attention_impl="xla", dtype=jnp.float32,
        moe=MoEConfig(num_experts=4, top_k=2),
    )
    opt = OptimizerConfig().build()
    state = create_train_state(cfg, opt, jax.random.PRNGKey(0), mesh)
    step = make_train_step(cfg, opt, mesh)
    batch = _batch(B=4, T=64, vocab=512)
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_moe_with_pipeline_parallelism():
    """MoE + pipeline: the router aux loss survives the microbatch loop
    (pipeline_apply(collect_aux=True)) instead of being dropped."""
    mesh = MeshConfig(data=2, stage=2, expert=2).build()
    cfg = gpt2.GPT2Config(
        vocab_size=512, max_seq_len=128, num_layers=4, num_heads=2,
        embed_dim=64, attention_impl="xla", dtype=jnp.float32, remat=False,
        moe=MoEConfig(num_experts=4, top_k=2),
    )
    params = gpt2.init_params(cfg, jax.random.PRNGKey(2))
    tokens = _batch(B=8, T=32, vocab=512)["tokens"][:, :-1]
    logits, aux = jax.jit(
        lambda p, t: gpt2.forward_pipelined(p, t, cfg, mesh,
                                            num_microbatches=2)
    )(params, tokens)
    assert np.isfinite(np.asarray(logits)).all()
    assert float(aux) > 0, "pipelined MoE must report a router aux loss"
    # and the full train step composes
    opt = OptimizerConfig().build()
    state = create_train_state(cfg, opt, jax.random.PRNGKey(0), mesh)
    step = make_train_step(cfg, opt, mesh, pipeline_microbatches=2)
    batch = _batch(B=8, T=64, vocab=512)
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_moe_dropless_routing_matches_topk():
    """Dropless mode: every token reaches its top-k experts; output is a
    convex combination of expert outputs (no capacity drops)."""
    from dataclasses import replace

    cfg = MoEConfig(num_experts=4, top_k=2, dropless=True)
    params = init_moe_params(jax.random.PRNGKey(0), 32, 64, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    out, aux = moe_layer(params, x, cfg)
    assert out.shape == x.shape and np.isfinite(np.asarray(out)).all()
    # with generous capacity, capacity routing converges to dropless
    cfg_cap = replace(cfg, dropless=False, capacity_factor=100.0)
    out_cap, _ = moe_layer(params, x, cfg_cap)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(out_cap), atol=1e-4, rtol=1e-4
    )


def _naive_xent(x, w, t, m=None):
    """The loss ``chunked_softmax_xent`` stands for, float32, whole logits."""
    logits = jnp.einsum("bte,ve->btv", x.astype(jnp.float32),
                        w.astype(jnp.float32))
    logp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(logp, t[..., None], -1)[..., 0]
    if m is None:
        return -ll.mean()
    return -(ll * m).sum() / jnp.maximum(m.sum(), 1)


def _prefix_mask(B, T, keep):
    return (jnp.arange(T)[None, :] < keep).astype(jnp.float32) * jnp.ones(
        (B, 1))


# what a case changes of: T=48, chunk=16, float32 x, no mask (``keep``: the
# rows a prefix mask keeps), the loss taken once, value's rtol and
# gradients' atol 1e-5
XENT_CASES = {
    "plain": {},
    "masked": {"keep": 30},
    # bf16 logits and dlogits: gradients of 4e-3 (dx) and 6e-2 (dW) at most
    # read within 1.5e-5 and 1e-4 of float32's
    "bf16-activations": {"dtype": jnp.bfloat16, "rtol": 1e-3, "atol": 5e-4},
    "ragged-tail": {"T": 50},
    "ragged-tail-masked": {"T": 50, "keep": 41},
    "one-chunk": {"chunk": 512},
    "all-masked": {"keep": 0},
    "cotangent-3": {"keep": 30, "factor": 3.0},
}


@pytest.mark.parametrize("case", list(XENT_CASES))
def test_chunked_xent_matches_naive(case):
    """Sequence-chunked cross entropy (no [B,T,V] materialization) must equal
    the naive log_softmax loss, values and gradients: the gradients its
    forward pass makes (``ops/xent.py``'s ``custom_vjp``) against autodiff
    of the naive loss in float32."""
    from ray_tpu.ops.xent import chunked_softmax_xent

    case = {"T": 48, "chunk": 16, "dtype": jnp.float32, "keep": None,
            "factor": 1.0, "rtol": 1e-5, "atol": 1e-5, **XENT_CASES[case]}
    T, chunk, dtype, keep, factor, rtol, atol = case.values()
    B, E, V = 2, 16, 97
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, E), jnp.float32)
    x = x.astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (V, E), jnp.float32) * 0.1
    t = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, V)
    m = None if keep is None else _prefix_mask(B, T, keep)

    def naive(x, w):
        return factor * _naive_xent(x, w, t, m)

    def chunked(x, w):
        return factor * chunked_softmax_xent(x, w, t, mask=m, chunk=chunk)

    # undifferentiated, differentiated, and under jit: one value
    want = np.asarray(naive(x, w))
    got, (gx, gw) = jax.value_and_grad(chunked, argnums=(0, 1))(x, w)
    for value in (chunked(x, w), got, jax.jit(chunked)(x, w)):
        np.testing.assert_allclose(
            np.asarray(value), want, rtol=rtol, atol=1e-7)
    wx, ww = jax.grad(naive, argnums=(0, 1))(x, w)
    assert gx.dtype == dtype and gx.shape == x.shape
    assert gw.dtype == jnp.float32 and gw.shape == w.shape
    np.testing.assert_allclose(
        np.asarray(gx, np.float32), np.asarray(wx, np.float32), atol=atol)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(ww), atol=atol)
    assert np.isfinite(np.asarray(gx, np.float32)).all()
    assert np.isfinite(np.asarray(gw)).all()
    if keep is not None:
        # a row the mask drops gets no gradient, exactly
        assert not np.asarray(gx, np.float32)[:, keep:].any()
        assert np.asarray(gx, np.float32)[:, :keep].any() == (keep > 0)
    if keep == 0:
        assert float(got) == 0.0 and not np.asarray(gw).any()


def test_chunked_xent_refuses_forward_mode():
    """Reverse mode, first derivatives: what ``ops/xent.py`` says it is."""
    from ray_tpu.ops.xent import chunked_softmax_xent

    x = jnp.ones((1, 8, 4))
    w = jnp.ones((5, 4))
    t = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda x: chunked_softmax_xent(x, w, t), (x,), (x,))


def test_tied_head_gradient_is_the_sum_of_both_uses():
    """GPT-2's ``wte`` is the embedding and the head: its gradient through
    ``loss_fn`` is the embedding's (a scatter-add, autodiff) plus the
    head's (made by the loss's forward pass), and the whole is autodiff's
    of the loss over whole logits."""
    from ray_tpu.models import decoder
    from ray_tpu.ops.xent import chunked_softmax_xent

    cfg = gpt2.GPT2Config(
        vocab_size=512, max_seq_len=64, num_layers=2, num_heads=2,
        embed_dim=64, dtype=jnp.float32, attention_impl="xla",
    )
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(B=2, T=32, vocab=512)
    inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]

    def apart(embedding, head):
        x, _ = decoder.forward_features(
            dict(params, wte=embedding), inputs, cfg)
        return chunked_softmax_xent(x, head, targets)

    def naive(p):
        logits, _ = gpt2.forward(p, inputs, cfg)
        return _naive_xent(logits, jnp.eye(512), targets)

    # each a program: op by op a backward pass takes many times as long
    tied = jax.jit(jax.grad(lambda p: gpt2.loss_fn(p, batch, cfg)))(params)
    as_embedding, as_head = jax.jit(jax.grad(apart, argnums=(0, 1)))(
        params["wte"], params["wte"])
    assert np.abs(np.asarray(as_embedding)).max() > 1e-4
    assert np.abs(np.asarray(as_head)).max() > 1e-4
    np.testing.assert_allclose(
        np.asarray(tied["wte"]), np.asarray(as_embedding + as_head),
        atol=1e-7)
    want = jax.jit(jax.grad(naive))(params)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(tied)[0],
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5,
            err_msg=jax.tree_util.keystr(path))


def _vocabulary_products(jaxpr, vocab, under=()):
    """(equation, names of the equations it lies inside) of every
    ``dot_general`` with an operand or result of the vocabulary's size."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars)):
            yield eqn, under
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _vocabulary_products(
                        inner, vocab, under + (eqn.primitive.name,))


@pytest.mark.parametrize("preset", ["gpt2-tiny", "smallthinker-tiny"])
def test_the_loss_head_is_three_products_a_chunk(preset):
    """The mechanism of ``ops/xent.py``: the differentiated loss projects a
    chunk's logits ONCE and forms dx and dW beside them, three products of
    the vocabulary's size inside one scan and none under a ``checkpoint``
    (a remat'd body ran the logits again: four); the loss nobody
    differentiates projects the logits and nothing else."""
    import dataclasses

    from ray_tpu.models import decoder, get_preset, module_for

    cfg = dataclasses.replace(get_preset(preset), attention_impl="xla")
    vocab = cfg.vocab_size
    assert vocab not in (cfg.embed_dim, cfg.max_seq_len, 96)
    params = jax.eval_shape(
        lambda: module_for(cfg).init_params(cfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 97), jnp.int32)}

    def loss(p, b):
        return decoder.loss_fn(p, b, cfg)

    plain = list(_vocabulary_products(
        jax.make_jaxpr(loss)(params, batch).jaxpr, vocab))
    assert len(plain) == 1
    assert not {"checkpoint", "remat", "remat2"} & set(plain[0][1])
    products = list(_vocabulary_products(
        jax.make_jaxpr(jax.grad(loss))(params, batch).jaxpr, vocab))
    assert len(products) == 3
    for eqn, under in products:
        assert "scan" in under, under
        assert not {"checkpoint", "remat", "remat2"} & set(under), under
    # logits [B, c, V] once; dx [B, c, E] and dW [V, E] from dlogits
    results = sorted(tuple(e.outvars[0].aval.shape) for e, _ in products)
    assert results == sorted(
        [(2, 96, vocab), (2, 96, cfg.embed_dim), (vocab, cfg.embed_dim)])
    # every product in the activation dtype, as autodiff gave them (an fsdp
    # mesh then reduces a chunk's dW in bf16: ``ops/xent.py``)
    assert {e.outvars[0].aval.dtype for e, _ in products} == {
        jnp.dtype(cfg.dtype)}


def test_loss_fn_chunked_matches_logits_path():
    """gpt2/llama loss_fn (now feature+chunked) must match the explicit
    logits-based computation."""
    from ray_tpu.models import llama

    for mod, cfg in (
        (gpt2, gpt2.GPT2Config(
            vocab_size=512, max_seq_len=64, num_layers=2, num_heads=2,
            embed_dim=64, dtype=jnp.float32, attention_impl="xla",
        )),
        (llama, llama.LlamaConfig(
            vocab_size=512, max_seq_len=64, num_layers=2, num_heads=4,
            num_kv_heads=2, embed_dim=64, dtype=jnp.float32,
            attention_impl="xla",
        )),
    ):
        params = mod.init_params(cfg, jax.random.PRNGKey(0))
        batch = _batch(B=2, T=32, vocab=512)
        loss = float(mod.loss_fn(params, batch, cfg))
        logits, aux = mod.forward(params, batch["tokens"][:, :-1], cfg)
        logp = jax.nn.log_softmax(logits, -1)
        tgt = batch["tokens"][:, 1:]
        ref = float(
            -jnp.take_along_axis(logp, tgt[..., None], -1)[..., 0].mean()
            + aux
        )
        assert abs(loss - ref) < 1e-4, (mod.__name__, loss, ref)


@pytest.mark.parametrize("preset", ["gpt2-tiny", "llama-tiny"])
def test_remat_keeps_the_flash_residuals(preset):
    """The one remat policy (``decoder._remat_policy``) saves the flash
    kernel's named residuals for every family, so a remat'd backward never
    runs the forward kernel again; loss and gradients are those of the step
    without remat. (llama's own policy knew neither name before ISSUE 29.)"""
    import dataclasses

    from ray_tpu.models import decoder, get_preset, module_for

    cfg = dataclasses.replace(
        get_preset(preset), dtype=jnp.float32,
        attention_impl="flash_interpret")
    params = jax.jit(module_for(cfg).init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    batch = _batch(B=2, T=128, vocab=cfg.vocab_size)

    def step(remat):
        c = dataclasses.replace(cfg, remat=remat)
        return jax.value_and_grad(lambda p: decoder.loss_fn(p, batch, c))

    loss, grads = jax.jit(step(True))(params)
    ref_loss, ref_grads = jax.jit(step(False))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-6
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), atol=1e-6, rtol=1e-5)
    jaxpr = str(jax.make_jaxpr(step(True))(params))
    assert "flash_out" in jaxpr and "flash_lse" in jaxpr
    # saved, not recomputed: the backward holds the one backward kernel and
    # no second forward one (2 pallas calls a layer body, not 3)
    assert jaxpr.count("pallas_call") == 2, jaxpr.count("pallas_call")


def test_optimizer_state_is_sharded_like_its_params(caplog):
    """Found on four real chips (PR 21): adam's mu/nu sat whole on device 0
    (1.49 GB there, 0.41 GB on the others) and step 2 compiled again because
    step 1 handed the state back sharded. The state must start out with the
    params' shardings, and the step must compile once."""
    config = gpt2.GPT2Config(
        vocab_size=128, max_seq_len=32, num_layers=2, num_heads=2,
        embed_dim=64, attention_impl="xla", dtype=jnp.float32,
    )
    mesh = MeshConfig(data=2, fsdp=2, tensor=2).build()
    opt = OptimizerConfig(warmup_steps=1, total_steps=4).build()
    state = create_train_state(config, opt, jax.random.PRNGKey(0), mesh)
    by_shape = {
        p.shape: p.sharding for p in jax.tree.leaves(state["params"])
    }
    for leaf in jax.tree.leaves(state["opt_state"]):
        assert len(leaf.sharding.device_set) == 8, leaf.sharding
        if leaf.ndim:
            assert leaf.sharding.is_equivalent_to(
                by_shape[leaf.shape], leaf.ndim
            )
    step = make_train_step(config, opt, mesh)
    tokens = jax.device_put(
        np.zeros((8, 17), np.int32),
        NamedSharding(mesh, P(("data", "fsdp"), None)),
    )
    with jax.log_compiles(), caplog.at_level("WARNING", logger="jax"):
        for _ in range(3):
            state, _ = step(state, {"tokens": tokens})
    compiles = [r for r in caplog.records
                if r.getMessage().startswith("Compiling jit(step_fn)")]
    assert len(compiles) == 1
