"""IBM's ``granitemoehybrid`` (Granite 4.0-H): what is peculiar to it. The
cases every family shares (the reference and each fault, bfloat16, the
refusals, the plan, padded chunks, idle and reused slots, two slots,
the prefix store of whole prompts, speculation refused) run over its row of
``tests/families.py``; here, the state layers' leaves where one engine hands
a prefilled cache to another, and the refusals of a narrower state.

CPU, float32, seeded weights, tiny widths: no device number.
"""
import dataclasses

import pytest

from ray_tpu.llm import DecodeEngine, LLMConfig, SamplingParams
from ray_tpu.models import granite_hybrid
from tests import families

FAMILY = "granite_hybrid"


def test_a_prefilled_state_is_handed_on_as_it_is():
    """``prefill_only`` on one engine, ``submit_prefilled`` on another: the
    pytree carries the state layers' leaves beside the keys and values."""
    (prompt,) = families.prompts_of(21, seed=-15)
    params = SamplingParams(max_new_tokens=8)
    with families.one_compile():
        whole = families._engine(FAMILY)
        want = list(whole.generate(prompt, params))
        handed = whole.prefill_only(prompt, params)
        whole.shutdown()
        assert sorted(handed["cache"]) == ["conv", "k", "ssm", "v"]
        other = families._engine(FAMILY)
        assert list(other.submit_prefilled(handed, params).result(
            timeout=600)) == want
        other.shutdown()


@pytest.mark.parametrize("key, value", [
    ("ssm_state_dtype", "bfloat16"), ("ssm_state_dtype", "float16"),
    ("mamba_proj_bias", True)])
def test_a_narrower_state_or_a_projection_bias_is_refused(key, value):
    """The cache holds a state in float32 and the kernel steps nothing else;
    the projections carry no bias: a configuration that states otherwise is
    told so, by the key, before anything is built."""
    with pytest.raises(ValueError, match=key):
        dataclasses.replace(granite_hybrid.GRANITE_HYBRID_TINY,
                            **{key: value})
    with pytest.raises(ValueError, match=key):
        DecodeEngine(LLMConfig(**{**families.TINY[FAMILY], key: value}))
