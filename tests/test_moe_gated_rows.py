"""A routed row meets its gate before the down product (``parallel/moe.py``,
PR 49): ``_experts`` multiplies a pair's gate into its hidden row, the
combine of ``_grouped_share`` adds the rows to their tokens and does nothing
else (``_down_add``, whose backward pass gathers the cotangent in the rows'
dtype), and nothing in the backward pass needs an expert's output. Since PR
52 a share's rows reach their tokens through ``ops/rows_to_tokens.py`` in
the combine and in the dispatch's backward pass (``_rows_of``), with no
select over the row buffer beside either.

Both dispatches are held to a dense float32 sum over experts that gates the
expert's OUTPUT, value and the gradients with respect to the tokens, the
three expert weights and the gates: one pass, under the ``lax.cond`` of a
share that may need further passes, with those passes forced, with a row
mask, with ``ragged_dot``, with the TPU's grouped products in interpret mode
and with the kernel that adds rows to tokens in interpret mode; then with
every row the grouped products owe nobody made NaN; then the lowered
backward pass is counted. CPU, tiny widths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoder, get_preset
from ray_tpu.ops import grouped_matmul, rows_to_tokens
from ray_tpu.parallel import moe

T, D, M, E, K = 96, 64, 32, 8, 2
F32, BF16 = jnp.float32, jnp.bfloat16

# name -> (experts held (None: ``_grouped``, all of them), the first, the
# activation, whether the router favours the share)
LAYERS = {
    "share-one-pass": (8, 0, "reglu", False),
    "share-under-the-cond": (2, 2, "reglu", False),
    "share-further-passes": (2, 2, "reglu", True),
    "share-gelu": (8, 0, "gelu", False),
    "grouped": (None, 0, "swiglu", False),
    "grouped-gelu": (None, 0, "gelu", False),
}


def _config(layer):
    held, first, activation, _ = LAYERS[layer]
    return moe.MoEConfig(num_experts=E, top_k=K, activation=activation,
                         dropless=True, num_held=held, first_held=first)


def _inputs(layer, masked, dtype):
    """(params, tokens, gates [T, K], chosen [T, K], row mask or None, the
    share's counts or None) of one layer."""
    config = _config(layer)
    held, first, _, favoured = LAYERS[layer]
    rng = np.random.default_rng(11)
    # weights of 0.1: hidden rows and results of the order of 1, so that a
    # tolerance is one on the values
    params = jax.tree.map(lambda a: a * 5.0, moe.init_moe_params(
        jax.random.PRNGKey(0), D, M, config))
    tokens = jax.random.normal(jax.random.PRNGKey(1), (T, D)).astype(dtype)
    chosen = np.stack([rng.permutation(E)[:K] for _ in range(T)])
    if favoured:    # nine tokens of ten send their first pair to the share
        to_share = rng.random(T) < 0.9
        own = first + rng.integers(0, held, T)
        chosen[:, 0] = np.where(to_share, own, chosen[:, 0])
        chosen[:, 1] = np.where(chosen[:, 1] == chosen[:, 0],
                                (chosen[:, 1] + 1) % E, chosen[:, 1])
    gates = jnp.asarray(rng.random((T, K)) + 0.25, F32)
    mask = jnp.asarray(rng.random(T) < 0.7) if masked else None
    counts = None
    if held is not None:
        pairs = chosen[np.asarray(mask)] if masked else chosen
        counts = jnp.asarray(np.bincount(pairs.ravel(), minlength=E)[
            first:first + held], jnp.int32)
        R = moe.held_rows_bound(T, config)
        assert (-(-T * K // R) > 1) == (held < E)
        assert (int(counts.sum()) > R) == favoured      # a second pass runs
    return params, tokens, gates, jnp.asarray(chosen, jnp.int32), mask, counts


def _program(layer, chosen, mask, counts):
    config = _config(layer)

    def f(params, tokens, gates):
        if config.num_held is None:
            return moe._grouped(params, tokens, gates, chosen, mask, config,
                                None)[0]
        return moe._grouped_share(params, tokens, gates, chosen, mask, counts,
                                  config, None)
    return f


def _dense(layer, chosen, mask):
    """Every held expert over every token in float32, its OUTPUT weighted by
    the token's gate for it (0 where the token did not choose it or is
    masked): the weights as the rows' dtype holds them."""
    config = _config(layer)
    held, first = config.num_held or E, config.first_held
    act = {"reglu": jax.nn.relu, "swiglu": jax.nn.silu}.get(config.activation)

    def f(params, tokens, gates):
        x = tokens.astype(F32)
        w = {n: p.astype(tokens.dtype).astype(F32) for n, p in params.items()}
        out = jnp.zeros((T, D), F32)
        for e in range(held):
            h = x @ w["expert_fc"][e]
            h = (act(x @ w["expert_gate"][e]) * h if act
                 else jax.nn.gelu(h))
            share = (gates * (chosen == first + e)).sum(-1)
            if mask is not None:
                share = share * mask
            out = out + share[:, None] * (h @ w["expert_out"][e])
        return out
    return f


CT = jax.random.normal(jax.random.PRNGKey(2), (T, D))


def _value_and_grads(f, params, tokens, gates):
    """(result [T, D], the gradients of <result, CT> with respect to the
    expert weights, the tokens and the gates)."""
    def loss(params, tokens, gates):
        out = f(params, tokens, gates)
        return (out.astype(F32) * CT).sum(), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(params, tokens, gates)
    weights = {n: g for n, g in grads[0].items() if n != "router_w"}
    return out, {**weights, "tokens": grads[1], "gates": grads[2]}


def _close(got, want, tol, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(want).max() > 0.05, what      # a tolerance on values
    np.testing.assert_allclose(
        got, want, rtol=tol, atol=tol * np.abs(want).max(), err_msg=what)


@pytest.fixture(params=["ragged_dot", "kernels", "rows-kernel"])
def impl(request, monkeypatch):
    """What runs the grouped products and adds rows to tokens: XLA both, the
    products' kernels interpreted, or the ``rows_to_tokens`` kernel
    interpreted (at these widths by a rule that takes every shape)."""
    if request.param == "kernels":
        monkeypatch.setattr(grouped_matmul, "_impl",
                            lambda: "pallas_interpret")
    if request.param == "rows-kernel":
        monkeypatch.setattr(rows_to_tokens, "_impl",
                            lambda: "pallas_interpret")
        monkeypatch.setattr(rows_to_tokens, "ROWS_A_VISIT", 0)
    return request.param


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("masked", [False, True], ids=["all-rows", "masked"])
@pytest.mark.parametrize("layer", list(LAYERS))
def test_both_dispatches_are_the_dense_sum_and_its_gradients(
        layer, masked, dtype, impl):
    """Value and the five gradients against the dense float32 sum: float32
    to 1e-5, bfloat16 to ``tests/test_grouped_matmul.py``'s 0.05 (of the
    largest value; the gradients come in their primal's dtype)."""
    params, tokens, gates, chosen, mask, counts = _inputs(
        layer, masked, dtype)
    tol = 1e-5 if dtype == F32 else 0.05
    with jax.default_matmul_precision("highest"):
        out, grads = _value_and_grads(
            _program(layer, chosen, mask, counts), params, tokens, gates)
        want, want_grads = _value_and_grads(
            _dense(layer, chosen, mask), params, tokens, gates)
    assert out.dtype == dtype and grads["gates"].dtype == F32
    assert grads["tokens"].dtype == dtype
    _close(out, want, tol, "result")
    assert set(grads) == set(want_grads) and len(grads) == (
        4 if layer.endswith("gelu") else 5)
    off = ~np.asarray(mask) if masked else np.zeros(T, bool)
    if masked and layer.startswith("grouped"):
        # a masked token's own gradient is what the products left in its
        # rows' (``_grouped``): nothing reads it
        grads["tokens"] = jnp.where(off[:, None], 0, grads["tokens"])
    for name, grad in grads.items():
        _close(grad, want_grads[name], tol, name)
    if masked:      # a masked token takes nothing and gives nothing
        assert not np.asarray(out, np.float32)[off].any()
        assert not np.asarray(grads["gates"])[off].any()
        if layer.startswith("share"):
            assert not np.asarray(grads["tokens"], np.float32)[off].any()


# ------------------------------------------------------ rows of no expert


def _poisoned(monkeypatch):
    """``moe``'s two doors to the grouped products with NaN in every row
    past ``group_sizes.sum()``, of a product's result and of its gradient
    with respect to the rows: what the contract leaves undefined there."""
    dot, dot_grads = moe.grouped_dot, moe.grouped_dot_grads

    def spoil(y, sizes):
        return jnp.where(
            (jnp.arange(y.shape[0]) < sizes.sum())[:, None], y, jnp.nan)

    def grads(lhs, rhs, sizes, ct, **kw):
        d_lhs, d_rhs = dot_grads(lhs, rhs, sizes, ct, **kw)
        return spoil(d_lhs, sizes), d_rhs

    def product(lhs, rhs, sizes, preferred_element_type=None, **kw):
        def plain(lhs, rhs, sizes):
            return dot(lhs, rhs, sizes, preferred_element_type, **kw)

        @jax.custom_vjp
        def f(lhs, rhs, sizes):
            return spoil(plain(lhs, rhs, sizes), sizes)

        def bwd(kept, ct):
            lhs, rhs, sizes = kept
            d_lhs, d_rhs = jax.vjp(
                lambda a, b: plain(a, b, sizes), lhs, rhs)[1](ct)
            return spoil(d_lhs, sizes), d_rhs, None

        f.defvjp(lambda *a: (f(*a), a), bwd)
        return f(lhs, rhs, sizes)

    monkeypatch.setattr(moe, "grouped_dot", product)
    monkeypatch.setattr(moe, "grouped_dot_grads", grads)


@pytest.mark.parametrize("layer", [
    "share-one-pass", "share-under-the-cond", "share-further-passes",
    "share-gelu", "grouped"])
def test_what_the_products_leave_in_rows_of_no_expert_reaches_nothing(
        layer, impl, monkeypatch):
    """With NaN wherever ``grouped_dot`` owes nothing (a share's buffer past
    its last pair, a masked token's pairs), the result and every gradient
    are finite and what they are without it. One exception, stated in
    ``_grouped``: a masked TOKEN's own gradient there, which nothing reads
    (the share zeroes it: it is the path that trains under a mask)."""
    params, tokens, gates, chosen, mask, counts = _inputs(layer, True, F32)
    program = _program(layer, chosen, mask, counts)
    want, want_grads = _value_and_grads(program, params, tokens, gates)
    _poisoned(monkeypatch)
    out, grads = _value_and_grads(program, params, tokens, gates)
    keep = np.asarray(mask) if layer == "grouped" else slice(None)
    for name, grad in {"result": out, **grads}.items():
        was = {"result": want, **want_grads}[name]
        if name == "tokens":
            grad, was = np.asarray(grad)[keep], np.asarray(was)[keep]
        assert np.isfinite(np.asarray(grad)).all(), name
        np.testing.assert_array_equal(
            np.asarray(grad), np.asarray(was), err_msg=name)


def test_the_poison_is_there(monkeypatch):
    """The test above tests something: the patched product's rows past the
    last group are NaN, forward and in the rows' gradient."""
    _poisoned(monkeypatch)
    sizes = jnp.asarray([3, 0, 4], jnp.int32)
    lhs, rhs = jnp.ones((12, 8)), jnp.ones((3, 8, 4))
    y, pull = jax.vjp(lambda a, b: moe.grouped_dot(a, b, sizes), lhs, rhs)
    d_lhs, d_rhs = pull(jnp.ones_like(y))
    for rows in (y, d_lhs, moe.grouped_dot_grads(
            lhs, rhs, sizes, jnp.ones_like(y))[0]):
        assert np.isfinite(np.asarray(rows)[:7]).all()
        assert np.isnan(np.asarray(rows)[7:]).all()
    assert np.isfinite(np.asarray(d_rhs)).all()


# ------------------------------------------- the lowered backward pass


def _equations(jaxpr, path=()):
    """(path of enclosing primitives, equation) of a jaxpr and of every
    jaxpr inside its equations."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(
                        inner, path + (eqn.primitive.name,))


# layer -> grouped products of a step (forward + backward) behind a residual
# stream: 3 forward and 6 transposes in the branch one pass takes (10 before
# PR 49: the down product again, for the gates' gradient), and under the
# cond the further passes' own 11 (3 forward, the two up-projections again
# under their own checkpoint, 6 transposes; 12 before); without a gate's
# product 2 forward and 4 transposes
PRODUCTS = {"share-one-pass": 9, "share-under-the-cond": 9 + 11,
            "share-gelu": 6}


@pytest.mark.parametrize("layer", list(PRODUCTS))
def test_the_backward_pass_makes_no_expert_output_again(layer, impl):
    """One routed layer under the block's checkpoint policy, bfloat16 rows,
    its result read by a LINEAR function (the residual stream): the down
    product ``-> f32[R, D]`` appears once a branch, forward, never in what a
    checkpoint runs again; every gather of ``[R, D]`` rows is in the rows'
    dtype, the cotangent's too; the backward pass makes no float32 ``[R,
    D]`` at all."""
    params, tokens, gates, chosen, mask, counts = _inputs(layer, False, BF16)
    config = _config(layer)
    R = moe.held_rows_bound(T, config)
    program = _program(layer, chosen, mask, counts)
    policy = decoder._remat_policy(dataclasses.replace(
        get_preset("smallthinker-tiny"), remat_policy="dots"))

    def loss(params, tokens, gates):
        return (program(params, tokens, gates).astype(F32) * CT).sum()

    jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(
        loss, policy=policy), argnums=(0, 1, 2)))(params, tokens, gates)
    product = "pallas_call" if impl == "kernels" else "ragged_dot_general"
    products = [(path, e) for path, e in _equations(jaxpr.jaxpr)
                if e.primitive.name == product]
    assert len(products) == PRODUCTS[layer]
    down = [path for path, e in products
            if e.outvars[0].aval.shape == (R, D)
            and e.outvars[0].aval.dtype == F32]
    branches = 2 if layer == "share-under-the-cond" else 1
    # once a branch, forward: never in what a checkpoint runs again
    assert len(down) == branches
    assert not [path for path in down if path and path[0] == "remat2"]
    assert not [e for path, e in _equations(jaxpr.jaxpr)
                if path and path[0] == "remat2" for v in e.outvars
                if v.aval.shape == (R, D) and v.aval.dtype == F32]
    gathers = [e.outvars[0].aval for _, e in _equations(jaxpr.jaxpr)
               if e.primitive.name == "gather"
               and e.outvars[0].aval.shape == (R, D)]
    # the rows, the rows again under the checkpoint, the cotangent's rows
    assert len(gathers) >= 3 * branches
    assert {a.dtype for a in gathers} == {jnp.dtype(BF16)}


# ---------------------------------------- rows reach their tokens (PR 52)


def _share_jaxpr(dtype):
    """The jaxpr of a differentiated ``_grouped_share`` at the routed
    training cell's shape (2 x 8,192 tokens of 2,560, 6 of 64 experts a
    token, 16 held of 768): traced over shapes, nothing runs."""
    config = moe.MoEConfig(num_experts=64, top_k=6, activation="reglu",
                           dropless=True, num_held=16, first_held=0)
    n_tok, width, hidden = 16384, 2560, 768

    def shape(*dims, dtype=dtype):
        return jax.ShapeDtypeStruct(dims, dtype)

    params = {"expert_fc": shape(16, width, hidden),
              "expert_gate": shape(16, width, hidden),
              "expert_out": shape(16, hidden, width)}

    def loss(params, tokens, gates, chosen, counts):
        out = moe._grouped_share(params, tokens, gates, chosen, None, counts,
                                 config, None)
        return out.astype(F32).sum()

    return moe.held_rows_bound(n_tok, config), jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)))(
            params, shape(n_tok, width), shape(n_tok, 6, dtype=F32),
            shape(n_tok, 6, dtype=jnp.int32), shape(16, dtype=jnp.int32))


@pytest.mark.parametrize("chosen", ["kernel", "scatter-add"])
def test_no_row_scatter_and_no_select_over_the_buffer_where_the_kernel_is(
        chosen, monkeypatch):
    """At the cell's shape the rule takes the kernel on a TPU: the
    differentiated share then holds no ``scatter-add`` whose updates are
    rows of ``D`` and no ``select_n`` over ``[R, D]`` in either branch, and
    ``rows_to_tokens`` four times (the combine and the dispatch's backward
    pass, in the branch one pass takes and in the further passes'). Off the
    TPU the two scatter-adds are there as they were, a branch, and still no
    select over the buffer."""
    if chosen == "kernel":
        monkeypatch.setattr(rows_to_tokens, "_impl", lambda: "pallas")
    R, jaxpr = _share_jaxpr(BF16)
    D = 2560
    assert R == 36864
    equations = [e for _, e in _equations(jaxpr.jaxpr)]
    kernels = [e for e in equations if e.primitive.name == "pallas_call"]
    scatters = [e for e in equations if e.primitive.name == "scatter-add"
                and e.invars[2].aval.shape[-1:] == (D,)]
    selects = [e for e in equations if e.primitive.name == "select_n"
               and e.outvars[0].aval.shape == (R, D)]
    assert not selects
    if chosen == "kernel":
        assert not scatters
        assert sorted(str(e.outvars[0].aval.dtype) for e in kernels) == [
            "bfloat16", "bfloat16", "float32", "float32"]
        assert {e.outvars[0].aval.shape for e in kernels} == {(16384, D)}
    else:
        assert not kernels and len(scatters) == 4
