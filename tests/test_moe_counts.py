"""The routed layer's integer counts (``parallel/moe.py:_count``): a compare
and a column sum where there was a scatter-add of ones, once a layer where a
share's layer counted twice. Held to the scatter histogram it replaces, to
``numpy.bincount`` over the router's own choice, and to the numbers the
layers of ``tests/test_smallthinker.py`` and ``tests/test_olmoe.py`` gave on
the commit before it (341d62a, where the scatter-adds counted). CPU."""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import get_preset, module_for
from ray_tpu.parallel import moe
from tests.families import _equations

E = 64           # experts the router scores
PAIRS = 96


def _scatter(expert, n):
    """What ``_count`` replaces, as the layer wrote it."""
    return jnp.zeros((n,), jnp.int32).at[jnp.asarray(expert)].add(
        1, mode="drop")


def _uniform(rng):
    return rng.integers(0, E, PAIRS)


def _out_of_range(rng):
    ids = rng.integers(0, E, PAIRS)
    ids[::5] = E                    # the layer's own "no expert"
    ids[1::7] = E + 1 + rng.integers(0, 1000, ids[1::7].shape)
    return ids


KINDS = {
    "uniform": _uniform,
    "one-expert": lambda rng: np.full(PAIRS, 17),
    "all-masked": lambda rng: np.full(PAIRS, E),
    "empty": lambda rng: np.zeros((0,), np.int64),
    "out-of-range": _out_of_range,
}
SHARES = {"first0": (0, 16), "first16": (16, 16), "first48": (48, 16),
          "all": (0, E)}


@pytest.mark.parametrize("share", list(SHARES))
@pytest.mark.parametrize("kind", list(KINDS))
def test_count_is_the_scatter_histogram_it_replaces(kind, share):
    first, held = SHARES[share]
    pairs = jnp.asarray(KINDS[kind](np.random.default_rng(7)), jnp.int32)
    every = jax.jit(moe._count, static_argnums=1)(pairs, E)
    assert every.dtype == jnp.int32 and every.shape == (E,)
    np.testing.assert_array_equal(every, _scatter(pairs, E))
    in_range = np.asarray(pairs)[np.asarray(pairs) < E]
    np.testing.assert_array_equal(every, np.bincount(in_range, minlength=E))
    # a share's count as ``_grouped_share`` made it: ids from its first
    # expert, the pairs of the others sent to the id past its last
    expert = pairs - first
    sent = jnp.where((expert >= 0) & (expert < held), expert, held)
    was = _scatter(sent, held)
    np.testing.assert_array_equal(every[first:first + held], was)
    np.testing.assert_array_equal(moe._count(sent, held), was)
    # an id below 0 counts nowhere either
    np.testing.assert_array_equal(moe._count(expert, held), was)


# ---------------------------------------- the layers' numbers, as they were


def _olmoe_layer(masked):
    """``tests/test_olmoe.py:_layer(64, 8, False)`` and its row mask."""
    cfg = moe.MoEConfig(num_experts=64, top_k=8, activation="swiglu",
                        norm_topk_prob=False, dropless=True)
    params = jax.tree.map(lambda a: a * 20.0, moe.init_moe_params(
        jax.random.PRNGKey(0), 16, 24, cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 16))
    mask = jnp.asarray(np.random.default_rng(3).random((2, 9)) < 0.4)
    return cfg, params, x, (mask if masked else None)


def _share_layer(first, held, masked):
    """A layer of ``tests/test_smallthinker.py``'s widths: 8 ReLU-gated
    experts, 2 a token, a share of ``held`` from ``first``, the router's
    logits handed in."""
    cfg = moe.MoEConfig(num_experts=8, top_k=2, activation="reglu",
                        dropless=True, num_held=held, first_held=first)
    params = moe.init_moe_params(jax.random.PRNGKey(0), 64, 32, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 96, 64))
    mask = jnp.asarray(np.random.default_rng(5).random((1, 96)) < 0.6)
    return cfg, params, x, (mask if masked else None)


# the layer (made in the test, not at import) and, on 341d62a, its
# (aux_loss, experts touched[, rows held, rows of the fullest held expert])
AS_IT_WAS = {
    "olmoe": ((_olmoe_layer, False), (0.01349105965346098, 55)),
    "olmoe-masked": ((_olmoe_layer, True), (0.014510980807244778, 37)),
    "share2-3": ((_share_layer, 2, 2, False),
                 (0.010020900517702103, 2, 49, 28)),
    "share6-7-masked": ((_share_layer, 6, 2, True),
                        (0.010022683069109917, 2, 24, 14)),
    "share0-7": ((_share_layer, 0, 8, False),
                 (0.010020900517702103, 8, 192, 31)),
}


def _case(name):
    (make, *args), was = AS_IT_WAS[name]
    return make(*args), was


@pytest.mark.parametrize("case", list(AS_IT_WAS))
def test_a_layers_counts_are_the_numbers_they_were(case):
    (cfg, params, x, mask), was = _case(case)
    _, aux, touched = moe.moe_layer_counted(params, x, cfg, row_mask=mask)
    # the router's own choice, counted by numpy
    tokens = x.reshape(-1, x.shape[-1])
    probs, _, chosen = moe._route(params, tokens, cfg, None, None)
    chosen = np.asarray(chosen)
    if mask is not None:
        chosen = chosen[np.asarray(mask).ravel()]
    every = np.bincount(chosen.ravel(), minlength=cfg.num_experts)
    want_aux = float(moe._aux_loss(
        probs, jnp.asarray(every / max(every.sum(), 1), jnp.float32), cfg))
    if cfg.num_held is None:
        got = (float(aux), int(touched))
        assert int(touched) == (every > 0).sum()
    else:
        held = every[cfg.first_held:cfg.first_held + cfg.num_held]
        got = (float(aux["aux_loss"]), int(touched),
               int(aux["moe_rows_held"]), int(aux["moe_rows_max_expert"]))
        assert got[1:] == ((held > 0).sum(), held.sum(), held.max())
    assert abs(got[0] - want_aux) < 1e-8
    assert got[1:] == was[1:] and abs(got[0] - was[0]) < 5e-7


MODEL_AS_IT_WAS = (0.040097907185554504, 142, 80)


def test_the_models_counts_are_the_numbers_they_were():
    """``tests/test_smallthinker.py``'s toy on its tokens, four layers
    summed: ``loss_fn(parts=True)`` on 341d62a."""
    cfg = get_preset("smallthinker-tiny")
    cfg = dataclasses.replace(cfg, dtype=jnp.float32, moe=dataclasses.replace(
        cfg.moe, num_held=2, first_held=2))
    model = module_for(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 512, (2, 33)), jnp.int32)
    # one program: op by op the same arithmetic takes ten times as long
    _, aux = jax.jit(lambda key, tokens: model.loss_fn(
        model.init_params(cfg, key), {"tokens": tokens}, cfg, parts=True))(
        jax.random.PRNGKey(0), tokens)
    assert (int(aux["moe_rows_held"]), int(aux["moe_rows_max_expert"])) == (
        MODEL_AS_IT_WAS[1:])
    assert abs(float(aux["aux_loss"]) - MODEL_AS_IT_WAS[0]) < 5e-7


# --------------------------------------------------------- no scatter of ones


@pytest.mark.parametrize("case", ["share2-3", "share6-7-masked", "olmoe"])
def test_the_forward_adds_no_ones_into_integers(case):
    (cfg, params, x, mask), _ = _case(case)
    jaxpr = jax.make_jaxpr(lambda p, x: moe.moe_layer_counted(
        p, x, cfg, row_mask=mask))(params, x)
    adds = [e for e in _equations(jaxpr.jaxpr)
            if e.primitive.name == "scatter-add"]
    # a share's rows are added back by one: the float32 sum of the combine
    assert len(adds) == (0 if cfg.num_held is None else 2)
    assert not [e for e in adds
                if jnp.issubdtype(e.invars[0].aval.dtype, jnp.integer)]


# ------------------------------------- the reader and the script, on a trace

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(CHECKOUT, "benchmarks", "tests", "data",
                        "v5e_1chip_smallthinker.xplane.pb")
FACTS = {"train_program": "jit_step_fn"}


@pytest.fixture
def recorded(monkeypatch):
    """PR 40's recording of the cell's step on the chip (its scatter-adds
    still count there)."""
    from benchmarks.lib import host_spans

    if not os.path.isfile(RECORDED):
        pytest.skip("no recorded trace of the cell's step")
    monkeypatch.setattr(host_spans, "TRACE_ROOT", RECORDED)


def test_the_dispatch_share_is_the_layers_share_less_the_experts(recorded):
    from benchmarks import run
    from benchmarks.lib import train_moe

    ns = train_moe.step_scope_ns(FACTS)
    share = run.read_layer_metric(
        "train.moe_dispatch_share_of_step", None, FACTS)
    whole = run.read_layer_metric("train.moe_share_of_step", None, FACTS)
    assert 0 < share < whole < 100
    assert share == pytest.approx(
        whole - 100.0 * ns["moe.experts"] / ns["total"])


def test_the_dispatch_share_of_a_dense_step_is_nothing(monkeypatch):
    from benchmarks import run
    from benchmarks.lib import host_spans

    dense = os.path.join(os.path.dirname(RECORDED), "v5e_1chip.xplane.pb")
    monkeypatch.setattr(host_spans, "TRACE_ROOT", dense)
    for program in ("jit_step_fn", "jit_train_step", "no such program"):
        assert run.read_layer_metric(
            "train.moe_dispatch_share_of_step", None,
            {"train_program": program}) is None


def test_the_script_prints_the_table_from_a_trace(recorded, capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_ops_by_scope",
        os.path.join(CHECKOUT, "scripts", "trace_ops_by_scope.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    assert script.main([RECORDED, "--top", "2"]) == 0
    out = capsys.readouterr().out
    for scope in ("moe.route", "moe.dispatch", "moe.experts", "moe.combine"):
        assert f"{scope}: " in out
    assert "ragged-dot-none" in out and "all moe.*: " in out
    # the recording is of the step that still counted by scatter-add
    assert re.search(r"fusion s32\[(16|64)\] scatter-add", out)
    # a forward operation directly under a scope: ``jvp(moe.route)/top_k``
    ops = script.op_scopes.load(RECORDED)
    forward = [m for m in ops.meta.values() if "jvp(moe." in m.op_name]
    assert forward and all(script.scope_of(m) == (
        script.SCOPE.search(m.op_name).group(1), False) for m in forward)
    assert script.main([os.path.dirname(RECORDED) + "/tiny"]) == 1
