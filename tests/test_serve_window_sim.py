"""``scripts/serve_window_sim.py``: the harness's schedule under a model of
the engine's loop. What is held here is the model's own arithmetic and the
two facts PR 51 read off it: with a tick that costs the same whatever is
live and admissions that cost nothing the seed moves nothing, and the
tick's slope over the live slots is what lets it."""
import importlib.util
import json
import os
import statistics
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "serve_window_sim", os.path.join(ROOT, "scripts", "serve_window_sim.py"))
sim = importlib.util.module_from_spec(spec)
sys.modules[spec.name] = sim    # a dataclass looks its module up by name
spec.loader.exec_module(sim)

MIX = {
    "rate_per_s": 2.0,
    "interarrival": {"dist": "exponential", "mean": 1.0},
    "prompt_tokens": {"dist": "lognormal", "median": 1024, "sigma": 1.0,
                      "min": 256, "max": 8192},
    "max_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.6,
                   "min": 64, "max": 768},
    "context_limit": 19456, "ramp_seconds": 4}
SEEDS = [3, 2147483901, 5100000603, 77, 123456789, 4242]


@pytest.mark.parametrize("prompt, chunks", [
    (256, [256]), (300, [512]), (2048, [2048]), (2500, [2048, 512]),
    (6000, [2048, 2048, 2048]), (16384, [2048] * 8)])
def test_an_admission_costs_its_chunks_buckets(prompt, chunks):
    engine = sim.Engine()
    want = sum(engine.admit_ms + engine.admit_us_per_token * 1e-3 * b
               for b in chunks)
    assert engine.admit(prompt) == pytest.approx(want)


def test_a_tick_grows_with_the_live_slots_and_bends():
    engine = sim.Engine()
    ticks = [engine.tick(n) for n in (0, 1, 16, 32, 64)]
    assert ticks[0] == engine.tick_ms
    assert ticks == sorted(ticks)
    # the held experts run out: the 64th slot costs less than the first
    assert ticks[4] - engine.tick(63) < ticks[1] - ticks[0]
    # nothing held, nothing touched
    assert sim.Engine(held=0).tick(10) == pytest.approx(
        engine.tick_ms + 10 * engine.slot_us * 1e-3)


def test_the_same_seed_reads_the_same_and_every_seed_the_same_work():
    engine = sim.Engine()
    first = sim.window(MIX, 7, engine, 20.0)
    assert first == sim.window(MIX, 7, engine, 20.0)
    other = sim.window(MIX, 8, engine, 20.0)
    assert other["requests"] == first["requests"] == 40
    assert other["per_token_p50_ms"] != first["per_token_p50_ms"]
    assert first["per_token_p50_ms"] <= first["per_token_p75_ms"]
    # a request's quotient is a tick and more: its admission, its waits
    assert first["per_token_mean_ms"] > engine.tick(1)


def test_a_flat_tick_and_free_admissions_leave_the_seed_nothing():
    flat = sim.Engine(tick_ms=5.0, slot_us=0.0, expert_us=0.0,
                      admit_ms=0.0, admit_us_per_token=0.0)
    medians = [sim.window(MIX, s, flat, 20.0)["per_token_p50_ms"]
               for s in SEEDS]
    assert max(medians) - min(medians) < 0.01 * statistics.median(medians)
    assert statistics.median(medians) == pytest.approx(5.0, rel=0.01)


def test_the_ticks_slope_is_what_the_seed_moves():
    def scatter(engine):
        medians = [sim.window(MIX, s, engine, 20.0)["per_token_p50_ms"]
                   for s in SEEDS]
        return statistics.pstdev(medians) / statistics.fmean(medians)

    sloped = scatter(sim.Engine())
    flat = scatter(sim.Engine(tick_ms=5.0, slot_us=0.0, expert_us=0.0))
    assert sloped > 2 * flat


@pytest.mark.parametrize("values, whole, less_one", [
    ([1.0, 2.0, 3.0, 4.0, 5.0, 6.0], 3.5 / 3.5, 3.0 / 3.0),
    ([5.0, 5.0, 5.0, 5.0, 5.0, 9.0], 1.0 / 5.0, 0.0)])
def test_spreads(values, whole, less_one):
    assert sim.spread(values) == pytest.approx(whole)
    assert sim.spread_without_farthest(values) == pytest.approx(less_one)


def test_the_new_cells_file_runs_through_the_model():
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           "ling-3.0-flash.serve-longgen.json")) as f:
        mix = json.load(f)["traffic"]
    got = sim.window(mix, 5100000607, sim.Engine())
    assert got["requests"] == round(mix["rate_per_s"] * 50) >= 90
    assert 4.0 < got["per_token_p50_ms"] < 8.0
    assert 5 < got["slots_per_tick"] < 20
