"""SmallThinker over the one decoder, on a share of a layer's experts: what
is peculiar to it. The cases every family shares (the logits against the
reference, bfloat16, the plan, padded chunks and cached steps, idle and
reused slots, two slots, speculation) run over its row of
``tests/families.py``; here, the loss and every leaf's gradient on a share
against its plain reference (``benchmarks/references/smallthinker.py``), a
layer in four shares, a share that drops no row, the up-projections held for
the backward pass, and the programs that stand. The toy keeps what is odd
about the published shapes: 7 query heads a kv head, a head width that is
not the model's, a window shorter than the sequence, a period of one global
layer and three window layers."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoder, get_preset, module_for
from ray_tpu.parallel import moe
from tests import families
from tests.families import _equations

T = 32


@pytest.fixture
def reference():
    """The reference with the toy's numbers in place of the published (the
    row's constants), a module of this test's own: a case sets its share."""
    return families.load_reference("smallthinker")


def _config(first=None, held=None, **kw):
    cfg = get_preset("smallthinker-tiny")
    share = {} if held is None else {"num_held": held, "first_held": first}
    return dataclasses.replace(
        cfg, dtype=jnp.float32, moe=dataclasses.replace(cfg.moe, **share),
        **kw)


def _batch(seed=0, batch=2, length=T + 1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 512, (batch, length)), jnp.int32)


def _reference_loss(ref, params, tokens):
    logits, aux = ref.logits_and_aux(params, tokens[:, :-1])
    lp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -ll.mean() + aux


@functools.lru_cache(maxsize=None)
def _reference_side(first, held):
    """The reference's loss and gradients on a share's initial weights, one
    program, once a share: the attention's implementation is the program's
    side alone."""
    reference = families.load_reference("smallthinker", FIRST_HELD=first or 0)
    cfg = _config(first, held)
    params = module_for(cfg).init_params(cfg, jax.random.PRNGKey(0))
    tokens = _batch()
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: _reference_loss(reference, p, tokens)))(params)


@pytest.mark.parametrize("impl", ["xla", "flash_interpret"])
@pytest.mark.parametrize("first, held", [(None, None), (2, 2), (6, 2)],
                         ids=["all", "experts2-3", "experts6-7"])
def test_loss_and_gradients_match_the_reference(impl, first, held):
    """The family's loss (cross entropy + auxiliary loss) and its gradient
    by every leaf, float32 on both sides with the kernels' window and
    grouped heads (``flash_interpret``) or XLA's. The two differ in the
    order of their sums: the loss reads within 2e-6 of 6.27, a leaf's
    gradient within 3e-7 where its largest entry is 1e-3 to 1e-2 (measured;
    the limits leave a factor of five). SiLU for ReLU reads 2e-4 on the
    experts' gradients, a router fed the normed input 1e-3 on the
    router's, a window ignored 2e-3 on wq. Each side is one program: op by
    op the same arithmetic takes a minute."""
    cfg = _config(first, held, attention_impl=impl)
    model = module_for(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    tokens = _batch()
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, {"tokens": tokens}, cfg)))(params)
    want, want_grads = _reference_side(first, held)
    assert abs(float(got) - float(want)) < 1e-5
    gaps = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                        got_grads, want_grads)
    sizes = jax.tree.map(lambda b: float(jnp.abs(b).max()), want_grads)
    for (path, gap), size in zip(
            jax.tree_util.tree_flatten_with_path(gaps)[0],
            jax.tree.leaves(sizes)):
        assert gap < 2e-6 + 1e-3 * size, (jax.tree_util.keystr(path), gap)


def test_the_aux_loss_and_the_counts_ride_beside_the_loss(reference):
    """``loss_fn(parts=True)``: the cross entropy apart from what the layers
    added up; of a share, the loss over ALL experts and the rows the share
    computed (a quarter of the pairs, give or take the routing)."""
    cfg = _config(2, 2)
    reference.FIRST_HELD = 2
    model = module_for(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    tokens = _batch()
    xent, aux = jax.jit(lambda p: model.loss_fn(
        p, {"tokens": tokens}, cfg, parts=True))(params)
    assert set(aux) == {"aux_loss", "moe_rows_held", "moe_rows_max_expert"}
    _, want_aux = jax.jit(reference.logits_and_aux)(params, tokens[:, :-1])
    assert abs(float(aux["aux_loss"]) - float(want_aux)) < 1e-6
    pairs = 2 * T * 2 * 4          # tokens x top_k x layers
    assert 0.1 * pairs < int(aux["moe_rows_held"]) < 0.5 * pairs
    assert (int(aux["moe_rows_held"]) / 2 <= int(aux["moe_rows_max_expert"])
            <= int(aux["moe_rows_held"]))
    total = jax.jit(lambda p: model.loss_fn(p, {"tokens": tokens}, cfg))(
        params)
    assert abs(float(total) - float(xent + aux["aux_loss"])) < 1e-6


def _one_layer(first, held):
    cfg = _config(first, held, num_layers=1, sliding_window_layout=(1,))
    return cfg, module_for(cfg)


def test_four_shares_of_a_layer_add_up_to_the_uncut_layer(reference):
    """The guide's section 4: a layer shared four ways, attention counted
    once. Each share's block gives ``base + its experts' part`` (``base``:
    the stream after attention, which every chip computes whole); the four
    parts and one base are the uncut reference's layer."""
    cfg, model = _one_layer(None, None)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, T, 64))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (2, T))
    layer = jax.tree.map(lambda a: a[0], params["blocks"]["segments"][0][0])
    experts = params["blocks"]["experts"]

    def block(cfg, experts):
        kind = decoder.layer_kinds(cfg)[0]
        # one program a share: op by op a block is seconds
        return jax.jit(lambda experts: decoder._body(cfg, None, pos, kind)(
            x, layer, None, (moe.stacked_for(experts, cfg.dtype), 0))[0])(
            experts)

    base = block(cfg, {**experts,
                       "expert_out": jnp.zeros_like(experts["expert_out"])})
    parts = []
    for first in range(0, 8, 2):
        share, _ = _one_layer(first, 2)
        held = {name: w if name == "router_w" else w[:, first:first + 2]
                for name, w in experts.items()}
        parts.append(block(share, held) - base)
    reference.LAYOUT, reference.FIRST_HELD = (1,), 0
    with jax.default_matmul_precision("highest"):
        want, _ = reference._layer(
            x, layer, jax.tree.map(lambda a: a[0], experts), True)
    np.testing.assert_allclose(np.asarray(base + sum(parts)),
                               np.asarray(want), atol=2e-5, rtol=0)
    # and each part is something: no share is the whole
    assert all(float(jnp.abs(p).max()) > 1e-4 for p in parts)


@pytest.mark.parametrize("sent", ["to_the_share", "elsewhere", "as_routed"])
def test_a_share_drops_no_row_whatever_the_routing(reference, sent):
    """Every token's every pair sent to the two held experts: four times the
    rows one pass of the grouped products holds (``held_rows_bound``), so
    the further passes run, and the result is still the reference's, with
    its gradients. Sent elsewhere, the share computes nothing."""
    config = moe.MoEConfig(num_experts=8, top_k=2, activation="reglu",
                           dropless=True, num_held=2, first_held=4)
    n = 96
    params = moe.init_moe_params(jax.random.PRNGKey(0), 64, 32, config)
    y = jax.random.normal(jax.random.PRNGKey(1), (1, n, 64))
    logits = jax.random.normal(jax.random.PRNGKey(2), (n, 8))
    push = {"to_the_share": 20.0, "elsewhere": -20.0, "as_routed": 0.0}[sent]
    logits = logits.at[:, 4:6].add(push)
    assert moe.held_rows_bound(n, config) == 72 < n * 2

    def program(params, y, logits):
        out, aux, _ = moe.moe_layer_counted(params, y, config, logits=logits)
        return out, aux

    def plain(params, y, logits):
        gates, _ = reference.route(logits, 2)
        return reference.experts_part(y[0], gates[:, 4:6], params)[None]

    got, aux = program(params, y, logits)
    rows = {"to_the_share": 2 * n, "elsewhere": 0}.get(sent)
    if rows is not None:
        assert int(aux["moe_rows_held"]) == rows
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(
            plain(params, y, logits)), atol=1e-6, rtol=0)
        grads = [jax.grad(lambda *a: (f(*a)[0] if f is program else f(*a)
                                      ).sum() * 3.0, argnums=(0, 1, 2))(
            params, y, logits) for f in (program, plain)]
    for a, b in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(grads[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("products_are", ["ragged_dot_general", "pallas_call"])
@pytest.mark.parametrize("held, passes, kept, again", [
    (8, 1, 10, 12), (2, 3, 24, 26)], ids=["one-pass", "under-the-cond"])
def test_the_up_projections_are_kept_for_the_backward_pass(
        held, passes, kept, again, products_are, monkeypatch):
    """Under the block's checkpoint policy the backward pass of a routed
    layer does not run the two up-projections again (named ``moe_fc`` /
    ``moe_gate``; a grouped product is no ``dot_general``): two grouped
    products fewer than under ``remat_policy="full"``, through the
    ``lax.cond`` of a share that may need further passes too (whose own 14,
    forward, remat and transposes, carry no name: an outer policy reaches
    through the checkpoint around a pass, and three passes' worth of
    residuals would be kept; 15 before PR 49, when the gates' gradient
    needed the experts' output). The down product does run again HERE, for
    this test's own loss, whose square needs the layer's result; behind a
    residual stream nothing does (``tests/test_moe_gated_rows.py``). The
    gradients are the same to the bit.
    ``pallas_call``: the same counts with the TPU's kernels forced (in
    interpret mode), whose ``custom_vjp`` keeps the operands it was given
    and nothing the policy would have to run a product again for."""
    if products_are == "pallas_call":
        from ray_tpu.ops import grouped_matmul
        monkeypatch.setattr(grouped_matmul, "_impl",
                            lambda: "pallas_interpret")
    config = moe.MoEConfig(num_experts=8, top_k=2, activation="reglu",
                           dropless=True, num_held=held, first_held=0)
    assert -(-96 * 2 // moe.held_rows_bound(96, config)) == passes
    params = moe.init_moe_params(jax.random.PRNGKey(0), 64, 32, config)
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 96, 64))

    def loss(params, y):
        out, aux, _ = moe.moe_layer_counted(params, y, config)
        return (out ** 2).sum() + aux["aux_loss"]

    grads, products = {}, {}
    for policy in ("dots", "full"):
        cfg = _config(remat_policy=policy)
        grad = jax.grad(jax.checkpoint(
            loss, policy=decoder._remat_policy(cfg)), argnums=(0, 1))
        products[policy] = sum(
            e.primitive.name == products_are
            for e in _equations(jax.make_jaxpr(grad)(params, y).jaxpr))
        grads[policy] = jax.jit(grad)(params, y)
    assert products == {"dots": kept, "full": again}
    for a, b in zip(jax.tree.leaves(grads["dots"]),
                    jax.tree.leaves(grads["full"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_share_is_dropless_and_inside_the_experts():
    with pytest.raises(ValueError, match="dropless"):
        moe.MoEConfig(num_experts=8, num_held=2)
    with pytest.raises(ValueError, match="experts 7 to 8 of 8"):
        moe.MoEConfig(num_experts=8, num_held=2, first_held=7, dropless=True)
    with pytest.raises(ValueError, match="every layer is routed"):
        dataclasses.replace(get_preset("smallthinker-tiny"), moe=None)


def test_the_step_reports_cross_entropy_and_what_rides_beside_it():
    """``make_train_step``: ``loss`` is the cross entropy, the auxiliary
    loss and the share's two counts have keys of their own, and the update
    is by the gradient of their sum."""
    from ray_tpu.train.step import (
        OptimizerConfig, create_train_state, make_train_step)

    cfg = _config(2, 2)
    opt = OptimizerConfig().build()
    state = create_train_state(cfg, opt, jax.random.PRNGKey(0))
    batch = {"tokens": _batch()}
    xent, aux = jax.jit(lambda p: module_for(cfg).loss_fn(
        p, batch, cfg, parts=True))(state["params"])
    _, metrics = make_train_step(cfg, opt, donate=False)(state, batch)
    # the step is one program and the parts another: the order of float32
    # sums, 1.4e-6 on a loss of 6.23 (measured with the parts op by op)
    assert abs(float(metrics["loss"]) - float(xent)) < 1e-5
    for key, value in aux.items():
        assert abs(float(metrics[key]) - float(value)) < 1e-5, key
    dense = get_preset("gpt2-tiny")
    state = create_train_state(dense, opt, jax.random.PRNGKey(0))
    _, metrics = make_train_step(dense, opt, donate=False)(state, batch)
    assert set(metrics) == {"loss", "grad_norm", "step"}


# ------------------------------------------------ the programs that stand
# Operations of the lowered programs of the three families that stood before
# this one, counted on the commit before it (0440a15) and unchanged by it:
# the pieces it added (``at_input``, a window and kv heads in the attention's
# dispatch, the auxiliary results as a tree) leave them as they were. PR 41:
# a routed layer's count is a compare and a column sum (``moe._count``) where
# it was a scatter-add of ones with its region and the clamp of its indices,
# five operations fewer a routed layer that the program spells out (llama's
# one scanned layer 541 -> 536, afmoe's two 1,096 -> 1,086); the names on the
# experts' two up-projections lower to nothing, so the dense two stand.
# PR 43: the loss makes its two gradients in its forward scan
# (``ops/xent.py``): no remat'd body and no transposed scan in the train
# step, 1,902 -> 1,843; the decode programs run no loss and stand.
# PR 49: a pair's gate meets its hidden row before the down product
# (``moe._experts``): the select that gives a row of no expert the gate 0
# (an iota, the counts' sum, a compare, a select over [rows]) and the
# expression's converts to float32 and back where the product over [rows, D]
# was, and ``_grouped`` zeroes a masked token's SUM where it zeroed its k
# rows before their gather (its own iota, sum and compare gone): as lowered,
# 7 operations more in llama's one scanned layer (536 -> 543) and 11 in
# afmoe's two (1,086 -> 1,097); compiled, they are elementwise over [rows]
# or inside the activation's fusion.
# PR 56: attention is heads-major: GPT-2's q, k and v are transposed behind
# its one fused product and its heads back before the output's
# (``gpt2.qkv`` / ``attn_out`` with ``heads_major=True``) where the flash
# calls' folds transposed them, and those folds are reshapes: 1,843 -> 1,850
# as lowered; the decode programs call the cached forward's pieces as they
# always did and stand.
STANDING = {"gpt2-tiny step": 1850, "gpt2 decode": 347, "llama decode": 543,
            "afmoe decode": 1097}
DECODE = {
    "gpt2": {},
    "llama": {"moe_num_experts": 4, "num_kv_heads": 2},
    "afmoe": {"moe_num_experts": 4, "num_kv_heads": 2, "sliding_window": 8,
              "layer_types": ["sliding_attention", "full_attention"],
              "moe_expert_bias_init_std": 0.02, "moe_score_func": "sigmoid"},
}


def _operations(text: str) -> int:
    return len(re.findall(r"= \"?(stablehlo\.[a-z_]+|func\.call)", text))


def test_the_gpt2_step_is_the_program_it_was():
    from ray_tpu.train.step import (
        OptimizerConfig, create_train_state, make_train_step)

    cfg = get_preset("gpt2-tiny")
    opt = OptimizerConfig().build()
    state = jax.eval_shape(
        lambda: create_train_state(cfg, opt, jax.random.PRNGKey(0)))
    step = make_train_step(cfg, opt, donate=False)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}
    assert _operations(step.lower(state, batch).as_text()) == STANDING[
        "gpt2-tiny step"]


@pytest.mark.parametrize("family", list(DECODE))
def test_jit_decode_is_the_program_it_was(family):
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.engine import engine_programs

    cfg = LLMConfig(model_family=family, num_heads=4, embed_dim=64,
                    num_layers=2, vocab_size=512, max_seq_len=64,
                    **DECODE[family]).model_config()
    model = module_for(cfg)
    params = jax.eval_shape(lambda: model.serving_params(
        cfg, model.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: decoder.init_kv_cache(cfg, 2, 64))
    lowered = engine_programs(cfg)[2].lower(
        params, jax.ShapeDtypeStruct((2,), jnp.int32), cache,
        jax.ShapeDtypeStruct((3, 2), jnp.int32))
    assert _operations(lowered.as_text()) == STANDING[family + " decode"]
