"""A serving cell's window on the CPU: the harness's own schedule
(``benchmarks/lib/traffic.py``, the same seed -> the same requests) driven
through a two-line model of the engine's loop, to read what the SEED alone
does to ``per_token_p50_ms`` before a chip is asked.

    python3 scripts/serve_window_sim.py <cell> --seeds 7 8 9
    python3 scripts/serve_window_sim.py <cell> --draw 300 [--rate 1.8]
        [--seconds 50] [--set key=value ...]   # a key of the tick's model

The model (``Engine``): a turn of the loop admits every request that is due,
one after another, each for ``admit_ms + admit_us_per_token`` x its chunks'
buckets with every live slot standing still, then decodes one token for all
N live slots in ``tick_ms + slot_us x N + expert_us x (held experts N rows
touch)``. Nothing else: no host, no proxy, no jitter. The defaults are
Ling-3.0-flash's one period on a v5e (PR 51: fitted by hand to the sweep's
three ticks, 3.78 / 5.12 / 10.8 ms at 4.6 / 13.2 / 56.2 live slots without
their admissions, and to 59 ms an admission in the mean); with them the
model reads the chip's own ``per_token_p50_ms`` of a seed with a median
error of 1.8% and a 90th percentile of 3.4% over the 48 windows of PR 51's
sets of six (PERF.md section 6), where two windows of ONE seed on the chip
are themselves 0.0-6.8% apart.
What it is for: the spread over seeds is a property of the traffic and of
the tick's slope over the live slots, and a few hundred seeds here cost
seconds. What it prints is a model's number, never a device's.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from dataclasses import dataclass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import loadgen, traffic  # noqa: E402


@dataclass
class Engine:
    tick_ms: float = 2.9            # a tick with nobody in it
    slot_us: float = 30.0           # a live slot's own bytes (its states)
    expert_us: float = 75.0         # one more held expert touched, all layers
    held: int = 128                 # experts the chip holds a layer
    held_picks: float = 2.0         # of a token's choices, those held here
    admit_ms: float = 6.0           # a prefill program, whatever its bucket
    admit_us_per_token: float = 27.0
    buckets: tuple = (256, 512, 1024, 2048)

    def tick(self, live: int) -> float:
        touched = self.held * (1 - (1 - 1 / self.held)
                               ** (self.held_picks * live)) if self.held else 0
        return (self.tick_ms + self.slot_us * 1e-3 * live
                + self.expert_us * 1e-3 * touched)

    def admit(self, prompt_tokens: int) -> float:
        ms, left = 0.0, prompt_tokens
        while left > 0:
            chunk = min(left, self.buckets[-1])
            bucket = next(b for b in self.buckets if b >= chunk)
            ms += self.admit_ms + self.admit_us_per_token * 1e-3 * bucket
            left -= chunk
        return ms


def window(mix: dict, seed: int, engine: Engine, seconds: float = 50.0) -> dict:
    """The runner's ramp + window (``serve_open_loop._drive``) under the
    model: each window request's (done - due) / tokens, and the loop's own
    averages inside the window."""
    ramp = float(mix["ramp_seconds"])
    measured = traffic.schedule(mix, seed, seconds)
    for r in measured:
        r.due_s += ramp
    requests = traffic.schedule(mix, seed ^ 0x5BD1E995, ramp) + measured
    order = sorted(range(len(requests)), key=lambda i: requests[i].due_s)
    opened, closed = ramp * 1e3, (ramp + seconds) * 1e3
    now, nxt, live, done = 0.0, 0, {}, {}
    ticks = slot_ticks = 0
    admitting = 0.0
    while len(done) < len(requests):
        while nxt < len(order) and requests[order[nxt]].due_s * 1e3 <= now:
            i = order[nxt]
            nxt += 1
            cost = engine.admit(requests[i].prompt_tokens)
            if opened <= now < closed:
                admitting += cost
            now += cost
            live[i] = requests[i].max_tokens - 1  # the prefill's own token
            if live[i] <= 0:
                done[i] = now
                del live[i]
        if not live:
            now = max(now, requests[order[nxt]].due_s * 1e3)
            continue
        now += engine.tick(len(live))
        if opened <= now < closed:
            ticks += 1
            slot_ticks += len(live)
        for i in list(live):
            live[i] -= 1
            if live[i] <= 0:
                done[i] = now
                del live[i]
    first = len(requests) - len(measured)
    per_token = [(done[i] - requests[i].due_s * 1e3) / requests[i].max_tokens
                 for i in range(first, len(requests))]
    return {"per_token_p50_ms": loadgen.percentile(per_token, 50),
            "per_token_p75_ms": loadgen.percentile(per_token, 75),
            "per_token_mean_ms": statistics.fmean(per_token),
            "requests": len(measured),
            "ms_per_tick": seconds * 1e3 / max(ticks, 1),
            "slots_per_tick": slot_ticks / max(ticks, 1),
            "admit_s": admitting / 1e3}


def spread(values) -> float:
    """First to third quartile over the median, as the PR instructions
    reckon a set's spread."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def spread_without_farthest(values) -> float:
    """The same with the run farthest from the median left out, as the
    driver's verdicts say it reckons one."""
    mid = statistics.median(values)
    return spread(sorted(values, key=lambda v: abs(v - mid))[:-1])


def main() -> None:
    import numpy as np

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--draw", type=int, default=0,
                    help="this many seeds drawn from PRNG 0, and the sets "
                         "of six among them")
    ap.add_argument("--rate", type=float)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--set", nargs="*", default=[], metavar="key=value")
    args = ap.parse_args()

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "workloads",
                           args.cell + ".json")) as f:
        mix = json.load(f)["traffic"]
    if args.rate:
        mix["rate_per_s"] = args.rate
    engine = Engine(**{k: float(v) for k, v in
                       (kv.split("=") for kv in args.set)})
    for seed in args.seeds:
        print(json.dumps({"seed": seed, **window(mix, seed, engine,
                                                 args.seconds)}))
    if args.draw:
        rng = np.random.default_rng(0)
        p50 = [window(mix, int(s), engine, args.seconds)["per_token_p50_ms"]
               for s in rng.integers(2 ** 30, 2 ** 31 + 1000, args.draw)]
        sets = [list(rng.choice(p50, 6, replace=False)) for _ in range(2000)]
        whole = np.array([spread(s) for s in sets])
        less_one = np.array([spread_without_farthest(s) for s in sets])
        print(json.dumps({
            "seeds": args.draw, "median_ms": statistics.median(p50),
            "sd_over_mean": statistics.pstdev(p50) / statistics.fmean(p50),
            "spread_of_all": spread(p50),
            "sets_of_six_spread_median": float(np.median(whole)),
            "sets_of_six_under_3pct": float((whole < 0.03).mean()),
            "sets_of_six_under_6pct": float((whole < 0.06).mean()),
            "farthest_left_out_spread_median": float(np.median(less_one)),
            "farthest_left_out_under_3pct": float((less_one < 0.03).mean()),
            "farthest_left_out_under_6pct": float((less_one < 0.06).mean()),
        }))


if __name__ == "__main__":
    main()
