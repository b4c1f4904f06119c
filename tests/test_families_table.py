"""The table of ``tests/families.py`` held to its word: a row for every
family of ``models.FAMILIES``, and a row added for a toy family on disk
collects what its layer kinds call for and no other case (the test side of
``test_models_registry.py::
test_a_new_family_reaches_the_engine_config_with_no_edit_outside_it``).
"""
import importlib
import inspect

from ray_tpu.models import FAMILIES
from tests import (
    families, test_family_cached, test_family_engine, test_family_reference,
)

SHARED = (test_family_reference, test_family_cached, test_family_engine)


# every shared case and what it says it is about (``families.shared_case``)
CASES = {name: case.properties for module in SHARED
         for name, case in vars(module).items() if hasattr(case, "properties")}


def _collected(family):
    """The shared cases a family's row collects."""
    return {name for name, wanted in CASES.items()
            if all(families.has(family, p) for p in wanted)}


def test_every_family_has_one_row_and_one_tiny_configuration():
    assert list(families.ROWS) == list(families.TINY) == list(FAMILIES)
    for family, keys in families.TINY.items():
        assert keys["model_family"] == family


def test_a_row_added_for_a_new_family_collects_what_its_kinds_call_for(
        monkeypatch, tmp_path):
    """A toy family on disk (llama's pieces under a ``Config`` of its own),
    one line of ``FAMILIES``, one row: it gets every case that asks for
    nothing, speculation verified (no state to roll back), none of the
    cases about a state, a share or a latent cache; and the cases run on
    it."""
    (tmp_path / "toy_family_row.py").write_text(families.TOY_FAMILY)
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.setitem(FAMILIES, "toy", "toy_family_row")
    importlib.import_module("toy_family_row")
    monkeypatch.setitem(families.TINY, "toy", dict(
        families.TINY["llama"], model_family="toy", moe_num_experts=0,
        toy_gain=2.0))
    monkeypatch.setitem(families.ROWS, "toy", families.Row(
        reference="olmoe", moved=families.ROWS["llama"].moved))
    assert len(CASES) == 17 and _collected("bailing_hybrid") == set(CASES) - {
        "test_speculation_verifies_against_the_plain_answer"}
    assert _collected("toy") == {name for name, wanted in CASES.items()
                                 if set(wanted) <= {"~state"}}
    assert _collected("toy") == _collected("gpt2")
    assert _collected("toy") < _collected("bailing_hybrid") | {
        "test_speculation_verifies_against_the_plain_answer"}
    # and a case of each shared file runs on the row as it stands
    for module, case in (
            (test_family_reference,
             "the_stack_is_the_period_and_the_cache_counts_by_kind"),
            (test_family_cached,
             "insert_writes_a_slot_of_leaves_of_every_rank"),
            (test_family_engine, "a_prompt_longer_than_the_largest_bucket"
                                 "_is_admitted_in_chunks")):
        run = getattr(module, f"test_{case}")
        run("toy", *[monkeypatch] * (
            "monkeypatch" in inspect.signature(run).parameters))
