"""The one general traffic generator: a cell's data file plus ``--seed``
give the schedule of requests. Nothing here knows a cell by name.

Every seed gets the SAME multiset of sizes and of inter-arrival gaps, in
another order: sizes and gaps are the distribution's own quantiles at
(i + 0.5) / n, not random draws, and the seed only permutes them. Two runs
with different seeds then do the same total work over the same span, and
what differs is which request meets which — the noise a deployment has —
not how much work the run drew.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of the distribution ``spec`` describes.

    - ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
      exp(N(ln m, s)) clipped to [a, b]
    - ``{"dist": "exponential", "mean": m}``
    - ``{"dist": "fixed", "value": v}``
    """
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "fixed":
        out = np.full(n, float(spec["value"]))
    elif kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        out = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif kind == "exponential":
        out = -np.log1p(-u) * spec["mean"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in spec or "max" in spec:
        out = np.clip(out, spec.get("min", -np.inf), spec.get("max", np.inf))
    return out


@dataclass
class Request:
    due_s: float        # seconds from the start of the schedule
    prompt: str         # one byte-tokenizer token per character
    prompt_tokens: int
    max_tokens: int


def schedule(mix: dict, seed: int, span_s: float) -> List[Request]:
    """Requests due in [0, span_s) at the mix's rate.

    ``mix`` keys: ``rate_per_s``, ``interarrival`` (a distribution with
    mean 1; scaled by 1/rate), ``prompt_tokens``, ``max_tokens``
    (distributions, rounded to whole tokens), ``context_limit`` (prompt +
    output may not exceed it: the output is cut, never the prompt).
    """
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * span_s)))
    rng = np.random.default_rng(seed)
    gaps = quantiles(mix["interarrival"], n)
    gaps = gaps / gaps.sum() * span_s  # n arrivals fill the span exactly
    prompts = np.rint(quantiles(mix["prompt_tokens"], n)).astype(int)
    outputs = np.rint(quantiles(mix["max_tokens"], n)).astype(int)
    # lengths of prompt and output are independent: pair them by a
    # permutation that does not depend on the seed, so the multiset of
    # (prompt, output) pairs is one and the same for every seed
    outputs = outputs[np.random.default_rng(n).permutation(n)]
    outputs = np.minimum(outputs, int(mix["context_limit"]) - prompts)
    order = rng.permutation(n)
    gaps = gaps[rng.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    letters = np.frombuffer(
        b"abcdefghijklmnopqrstuvwxyz ABCDEFGHIJKLMNOPQRSTUVWXYZ.,", np.uint8)
    out = []
    for t, i in zip(due, order):
        text = letters[rng.integers(0, len(letters), int(prompts[i]))]
        out.append(Request(float(t), text.tobytes().decode(),
                           int(prompts[i]), int(outputs[i])))
    return out
