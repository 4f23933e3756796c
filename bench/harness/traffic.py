"""One general generator for every traffic mix.

A mix is a JSON file of parameters under ``bench/traffic``:

    source        the public trace or dataset its lengths come from, and
                  which statistics of it are used
    loop          "open" (arrivals on a schedule) or "closed" (``clients``
                  each send their next request when the last completes)
    rate_per_s    open loop: mean arrival rate
    arrival_cv    open loop: coefficient of variation of the gaps between
                  arrivals, gamma-distributed (1, the default: Poisson;
                  above 1: burstier)
    clients       closed loop: how many callers
    prompt/output {"dist": "lognormal", "median", "sigma", "min", "max"}
                  or {"dist": "uniform", "min", "max"}: token counts,
                  clipped to [min, max]
    requests      how many requests to draw (open loop: default a quarter
                  more than the mean arrivals over the lead-in and window)
    lead_in_s     traffic before the measured window opens
    trace_s       how much of the window a traced run records

The lengths and gaps are independent random draws from these
distributions: one sample path of the arrival process and an i.i.d.
sample of lengths, drawn once per mix and request count from a fixed seed
and replayed in that order, as a recorded trace is.  ``--seed`` draws the
prompt tokens (and the harness the weights), so every seed offers the same
work at the same times: the spread between runs measures the system, and
the arrivals inside a run keep all the burstiness of the process.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

SET_SEED = 20250317   # fixed: the sizes and gaps never depend on --seed


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: np.ndarray        # int32 token ids
    max_new: int
    gap_s: float = 0.0        # open loop: time after the previous arrival


def _lengths(dist: dict, n: int, rng) -> np.ndarray:
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        x = rng.uniform(lo, hi, n)
    elif dist["dist"] == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(n))
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _unit_gaps(cv: float, n: int, rng) -> np.ndarray:
    """Gaps of mean 1 with coefficient of variation ``cv`` (gamma; cv 1 is
    the exponential of a Poisson process)."""
    shape = 1.0 / (cv * cv)
    return rng.gamma(shape, 1.0 / shape, n)


def requests_needed(mix: dict, seconds: float) -> int:
    """How many requests to draw: ``requests`` where the mix states it,
    else a quarter more than the open loop's mean arrivals over the lead-in
    and the window.  A run that outlasts them sends the same set again, in
    the same order."""
    if "requests" in mix:
        return int(mix["requests"])
    return int(math.ceil(1.25 * mix["rate_per_s"]
                         * (mix["lead_in_s"] + seconds))) + 8


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    n = requests_needed(mix, seconds)
    fixed = [np.random.default_rng(s) for s in
             np.random.SeedSequence([SET_SEED, n]).spawn(3)]
    prompts = _lengths(mix["prompt"], n, fixed[0])
    outputs = _lengths(mix["output"], n, fixed[1])
    if mix["loop"] == "open":
        gaps = _unit_gaps(mix.get("arrival_cv", 1.0), n, fixed[2]) \
            / mix["rate_per_s"]
    else:
        gaps = np.zeros(n)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    return [Request(i, rng.integers(0, vocab, int(p), dtype=np.int32),
                    int(o), float(g))
            for i, (p, o, g) in enumerate(zip(prompts, outputs, gaps))]
