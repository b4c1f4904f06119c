"""Rows added to their tokens (``ops/rows_to_tokens.py``, PR 52): the Pallas
kernel, interpreted on the CPU with NaN in every byte of VMEM it has not
written, against ``zeros.at[index].add(rows, mode="drop")``; then the rule
that says which shapes it takes. Tiny tiles (16 tokens, 8 rows a round) so
that a case has several tiles and rounds. Nothing here is a time."""
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import rows_to_tokens as rt

F32, BF16 = jnp.float32, jnp.bfloat16
T, G, D = 64, 4, 256
TILING = (16, 8)        # tokens a tile, rows of a group's run a round
INTERPRET = pltpu.InterpretParams(uninitialized_memory="nan")


def _pairs(case, rng):
    """case -> the (token, group) pairs of a routing, a token naming a group
    at most once."""
    if case == "one-group":             # every row in one group
        return [(t, 2) for t in range(T)]
    pairs = []
    for t in range(T):
        groups = rng.permutation(2 * G)[:2]     # half the experts are held
        if case == "empty-groups":      # groups 0 and 2 get nothing
            groups = [g for g in groups if g not in (0, 2)]
        if case == "full-tile" and 16 <= t < 32:
            # group 1 fills tile 1: 16 rows of one run, two rounds of 8
            groups = [1] + [g for g in groups if g != 1][:1]
        if case == "empty-tile" and 32 <= t < 48:
            groups = []                 # no row lands in tile 2
        pairs += [(t, g) for g in groups if g < G]
    return pairs


def _buffer(case, dtype, seed=0):
    """(rows [R, D], token [R], sizes [G], how many rows are real) of a
    case: the pairs in the order a stable sort by group leaves, NaN in every
    row past the last and any token beside it."""
    rng = np.random.default_rng(seed)
    pairs = sorted(_pairs(case, rng), key=lambda p: (p[1], p[0]))
    counts = np.bincount([g for _, g in pairs], minlength=G)
    start = 0
    if case == "overflow-pass":
        # the second pass of a share whose passes hold 24 rows: the sizes
        # ``_grouped_share.one`` makes, the first groups empty or cut
        start, held = 24, 24
        ends = np.cumsum(counts)
        counts = (np.clip(ends - start, 0, held)
                  - np.clip(ends - counts - start, 0, held))
    total = int(counts.sum())
    pairs = pairs[start:start + total]
    # the last runs' copies are clamped inside a buffer that ends with them;
    # one shorter than a single copy (16 rows) is padded
    R = {"clamped": -(-total // 8) * 8, "short": 8}.get(case, total + 40)
    if case == "short":
        pairs, counts, total = pairs[:5], np.array([5, 0, 0, 0]), 5
        pairs = [(t, 0) for t, _ in pairs]
    rows = rng.standard_normal((R, D)).astype(np.float32)
    rows[total:] = np.nan
    token = rng.integers(0, T + 1, R)
    token[:total] = [t for t, _ in pairs]
    return (jnp.asarray(rows, dtype), jnp.asarray(token, jnp.int32),
            jnp.asarray(counts, jnp.int32), total)


def _reference(rows, token, total):
    """The float32 scatter-add of the real rows, and of their magnitudes."""
    index = jnp.where(jnp.arange(rows.shape[0]) < total, token, T)
    clean = jnp.where((jnp.arange(rows.shape[0]) < total)[:, None],
                      rows.astype(F32), 0)
    zeros = jnp.zeros((T, D), F32)
    return (np.asarray(zeros.at[index].add(clean, mode="drop")),
            np.asarray(zeros.at[index].add(jnp.abs(clean), mode="drop")),
            np.bincount(np.asarray(token[:total]), minlength=T))


CASES = ["random", "empty-groups", "full-tile", "one-group", "empty-tile",
         "clamped", "short", "overflow-pass"]


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_kernel_is_the_scatter_add_of_the_real_rows(case, dtype):
    """Float32 rows: the reference within the reordering of a token's few
    sums (4 ulp of the magnitudes' sum), and bit for bit where a token has
    one row. bfloat16 rows: the float32 sum rounded once. Nothing of the
    NaN past the last row, in the buffer or in VMEM, reaches the result; a
    tile no row lands in comes back zeros."""
    rows, token, sizes, total = _buffer(case, dtype)
    got = np.asarray(rt._kernel(
        (rows,), token, sizes, T, jnp.dtype(dtype), tiling=TILING,
        interpret=INTERPRET).astype(F32))
    want, magnitude, rows_a_token = _reference(rows, token, total)
    assert np.isfinite(got).all()
    assert not got[rows_a_token == 0].any()
    if dtype == BF16:
        np.testing.assert_array_equal(
            got, np.asarray(jnp.asarray(want).astype(BF16).astype(F32)))
        return
    np.testing.assert_array_equal(
        got[rows_a_token == 1], want[rows_a_token == 1])
    assert (np.abs(got - want) <= 4 * np.finfo(np.float32).eps
            * magnitude).all()
    if case == "empty-tile":
        assert not got[32:48].any() and got[:32].any()
    if case == "full-tile":
        assert int(sizes[1]) >= 16 > TILING[1]


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_several_buffers_are_added_in_float32(dtype):
    """Two buffers of one gather's cotangents: the result of their sum, made
    in float32 and rounded once (bfloat16: bit for bit the float32 sum of
    all the rows, where a sum written out in bfloat16 first would differ),
    the NaN past the last row of either reaching nothing."""
    rows, token, sizes, total = _buffer("random", dtype)
    other = jnp.roll(rows, 3, axis=1) * 1.5
    got = np.asarray(rt._kernel(
        (rows, other), token, sizes, T, jnp.dtype(dtype), tiling=TILING,
        interpret=INTERPRET).astype(F32))
    one, size_one, _ = _reference(rows, token, total)
    two, size_two, _ = _reference(other, token, total)
    assert np.isfinite(got).all()
    if dtype == BF16:
        np.testing.assert_array_equal(got, np.asarray(
            jnp.asarray(one + two).astype(BF16).astype(F32)))
        early = np.asarray(_reference(rows + other, token, total)[0])
        assert (got != np.asarray(
            jnp.asarray(early).astype(BF16).astype(F32))).any()
    else:
        assert (np.abs(got - (one + two)) <= 4 * np.finfo(np.float32).eps
                * (size_one + size_two)).all()
    # the door, off the TPU: the scatter-add of the sum
    plain = rt.rows_to_tokens((rows, other), token, sizes, T, F32)
    np.testing.assert_allclose(np.asarray(plain), one + two,
                               rtol=0.05, atol=0.05)


def test_the_cases_are_what_they_say():
    """A group of no rows, a run longer than a round, a buffer that ends
    with its last run, one shorter than a copy, sizes that start inside a
    group."""
    sizes = {case: np.asarray(_buffer(case, F32)[2]) for case in CASES}
    assert sizes["empty-groups"][0] == 0 == sizes["empty-groups"][2]
    assert (sizes["one-group"] == [0, 0, T, 0]).all()
    rows, _, _, total = _buffer("clamped", F32)
    assert rows.shape[0] - total < 8
    whole = sizes["random"]
    assert sizes["overflow-pass"].sum() == 24
    assert sizes["overflow-pass"][0] in (0, max(whole[0] - 24, 0))
    assert (sizes["overflow-pass"] <= whole).all()
    assert (sizes["overflow-pass"] < whole).sum() >= 2


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_the_door_takes_the_kernel_where_the_rule_says(dtype, monkeypatch):
    """``rows_to_tokens`` itself: XLA's scatter-add off the TPU and where a
    visit finds too few rows (the sums in the rows' dtype, as they were),
    the kernel where the rule engages; both leave the rows past the last
    group out."""
    rows, token, sizes, total = _buffer("random", dtype)
    want, _, _ = _reference(rows, token, total)
    plain = rt.rows_to_tokens(rows, token, sizes, T)
    assert plain.dtype == dtype
    tol = 1e-6 if dtype == F32 else 0.05
    np.testing.assert_allclose(np.asarray(plain, np.float32), want,
                               rtol=tol, atol=tol)
    monkeypatch.setattr(rt, "_impl", lambda: "pallas_interpret")
    calls = []
    kernel = rt._kernel
    monkeypatch.setattr(rt, "_kernel", lambda *a, **kw: (
        calls.append(a[0][0].shape), kernel(*a, **kw))[1])
    monkeypatch.setattr(rt, "ROWS_A_VISIT", 1000)
    assert not rt.engages(rows.shape[0], G, T, D, dtype)
    rt.rows_to_tokens(rows, token, sizes, T)
    assert not calls
    monkeypatch.setattr(rt, "ROWS_A_VISIT", 1)
    got = rt.rows_to_tokens(rows, token, sizes, T, F32)
    assert calls == [rows.shape] and got.dtype == F32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


# (rows of the buffer, groups, tokens) -> whether the kernel takes the call:
# the routed training cell's layer, then Ling-3.0-flash's tick of 64 slots,
# its 512-token bucket and its 2,048-token chunk (PERF.md section 6, PR 52:
# the four timed on the chip; the kernel won the three it takes)
RULE = [((36864, 16, 16384), True), ((192, 128, 64), False),
        ((1536, 128, 512), True), ((6144, 128, 2048), True)]


@pytest.mark.parametrize("shape, taken", RULE, ids=str)
def test_the_rule_over_static_shapes(shape, taken):
    R, groups, tokens = shape
    for dtype in (F32, BF16):
        assert rt.engages(R, groups, tokens, 2560, dtype) == taken
    Tt, C = rt.tiles(R, groups, tokens)
    assert Tt == min(512, tokens) and C % 8 == 0
    # a visit's payload holds the buffer's rows a visit
    assert C * groups * -(-tokens // Tt) >= R


def test_bounds_are_the_runs_of_each_tile():
    """``_bounds`` against a count in numpy: entry (tile, g) is the first
    row of group g whose token is at or past the tile's first."""
    rows, token, sizes, total = _buffer("random", F32)
    Tt = TILING[0]
    got = np.asarray(rt._bounds(token, sizes, Tt, T // Tt)).reshape(-1, G)
    ends = np.cumsum(np.asarray(sizes))
    starts = ends - np.asarray(sizes)
    tok = np.asarray(token)
    for tile in range(T // Tt + 1):
        for g in range(G):
            mine = tok[starts[g]:ends[g]]
            assert got[tile, g] == starts[g] + (mine < tile * Tt).sum()
    assert got[-1, -1] == total
