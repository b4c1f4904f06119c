"""The comparison of ``runners/serve_open_loop_median.py`` on made-up
numbers: what each of its two limits sees, and that its log-probabilities
are ``lib/reference.py``'s."""
import numpy as np
import pytest

from benchmarks.lib import reference
from benchmarks.runners import serve_open_loop_median as runner

MIX = {"logprob_tolerance": 0.9, "logprob_median_tolerance": 0.012,
       "logprob_request_median_tolerance": 0.06}


def _request(rng, n=24, floor=0.01, flips=0, by=0.4):
    gaps = np.abs(rng.normal(0, floor, n))
    gaps[rng.choice(n, flips, replace=False)] += by
    return gaps


@pytest.mark.parametrize("case, gaps_of, want", [
    # a sound run: bf16's rounding, and a few tokens whose expert flipped
    ("flips", lambda rng: [_request(rng, flips=4) for _ in range(3)], True),
    # a lower precision: every token a little off, none of them far
    ("every_token", lambda rng: [_request(rng, floor=0.03)] * 3, False),
    # a fault beyond the window: the long request alone, all its tokens;
    # the median of all three requests' tokens is one of the sound ones
    ("one_request", lambda rng: [_request(rng), _request(rng),
                                 _request(rng, floor=0.3)], False),
    # one token far off in an otherwise sound run
    ("one_token", lambda rng: [_request(rng, flips=1, by=1.5),
                               _request(rng), _request(rng)], False),
])
def test_each_limit_sees_its_kind_of_fault(case, gaps_of, want):
    read = runner.readings(gaps_of(np.random.default_rng(0)))
    assert runner.within(read, [], MIX) is want, read
    if case == "flips":   # the largest gap alone could not tell these
        assert read["max_abs_logprob_diff"] > 0.3
        assert max(read["request_median_abs_logprob_diff"]) < 0.02
    if case == "every_token":
        assert read["max_abs_logprob_diff"] < 0.3
        assert max(read["request_median_abs_logprob_diff"]) < 0.06
    if case == "one_request":
        assert read["median_abs_logprob_diff"] < 0.012
    if case == "one_token":
        assert read["median_abs_logprob_diff"] < 0.012


def test_an_eos_gap_is_held_to_the_largest_gaps_limit():
    read = runner.readings([np.zeros(4)])
    assert runner.within(read, [0.5], MIX)
    assert not runner.within(read, [1.0], MIX)
    assert runner.readings([np.zeros(0)])["max_abs_logprob_diff"] == 0.0


def test_log_probabilities_and_greedy_gaps_are_the_librarys():
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(0, 2, (11, 7, 50)), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, 50, (2, 8)), jnp.int32)

    def logits(params, toks):   # any function of the tokens before
        return params[toks[:, :1] % 11, jnp.arange(toks.shape[1])[None]]

    for mine, theirs in ((runner.chosen_logprobs, reference.token_logprobs),
                         (runner.greedy_gaps, reference.greedy_gaps)):
        np.testing.assert_allclose(mine(logits, table, tokens),
                                   theirs(logits, table, tokens), atol=1e-5)


def test_answer_gaps_reads_the_rows_after_each_prompt():
    import jax.numpy as jnp

    V = 16

    def logits(params, toks):   # log p(next = t + 1 mod V) is near 0
        return 20.0 * jnp.eye(V)[(toks + 1) % V]

    prompt, answer = [3, 4, 5], [6, 7, 9]   # 9 is not what follows 7
    want = np.asarray(runner.reference_rows(
        logits, None, runner.chosen_logprobs, [prompt + answer], 8))[0]
    assert want.shape == (7,) and abs(want[2]) < 1e-6 and want[4] < -19
    gaps, = runner.answer_gaps(
        logits, None, [(prompt, answer, [0.0, -0.25, 0.0])], 8)
    np.testing.assert_allclose(gaps, [0.0, 0.25, 20.0], atol=1e-5)
