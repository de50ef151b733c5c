"""The one traffic generator: a mix's data file in, seeded requests out.

A traffic mix is a JSON file under ``bench/traffic/``; this module reads
its parameters and nothing else decides what is sent.  Keys:

``arrivals``
    ``rate_per_s`` (constant), or ``rate_profile``: ``[[seconds, rate],
    ...]`` repeated cyclically (bursts, ramps).  Arrival times come from an
    inhomogeneous Poisson process sampled by Lewis-Shedler thinning.
``prompt_tokens`` / ``output_tokens``
    a length law: ``{"law": "lognormal", "median", "sigma", "min", "max"}``
    or ``{"law": "uniform", "min", "max"}``.
``round_prompt_to_chunk``
    round each prompt up to whole prefill chunks (see ``PERF.md``).
``warm``
    set-up traffic served before the window: ``{"fill_slots": true}`` fills
    every slot with requests caught mid-decode in a steady state of long
    requests: each one's length drawn from the length-biased law of
    ``output_tokens``, its age (the tokens it has already made) a uniform
    share of that length, given as prompt tokens after its own prompt, and
    the rest its output budget, so the window opens at steady-state
    contexts; ``{"requests": n, "prompt": p, "output": o}`` sends ``n``
    short requests that complete before the window (warming the programs
    and every slot).
``drain_s``
    seconds past the window's close to wait for first tokens of requests
    due in the window (0: none).
``pattern_seed``
    seeds the arrival times and sizes.  The run's ``--seed`` draws the
    token ids (and, elsewhere, the weights), so every seed offers the same
    amount of work at the same times: which prompt sizes reach the window
    would otherwise change with the seed and move its numbers.

Every request is greedy (temperature 0), so served tokens can be checked
against the reference's logits.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List

import numpy as np

# sizes drawn for every run (a window uses the first of them, one per
# arrival); no window at the rates the mixes name has more arrivals
SET_SIZE = 4096


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it (times relative to the
    window's opening; warm requests have ``due = 0`` and ``warm = True``)."""

    rid: int
    due: float
    prompt: np.ndarray
    max_new: int
    warm: bool = False


def sample_arrival_times(rate_fn: Callable[[float], float], horizon: float,
                         rng: np.random.Generator,
                         rate_max: float) -> np.ndarray:
    """Inhomogeneous-Poisson arrival times on [0, horizon) by thinning."""
    times = []
    t = 0.0
    while True:
        t += rng.exponential(1.0 / rate_max)
        if t >= horizon:
            break
        if rng.random() < rate_fn(t) / rate_max:
            times.append(t)
    return np.asarray(times)


def rate_function(arrivals: Dict):
    """(rate_fn, rate_max) of a mix's ``arrivals`` block."""
    if "rate_profile" in arrivals:
        prof = [(float(d), float(r)) for d, r in arrivals["rate_profile"]]
        period = sum(d for d, _ in prof)

        def fn(t: float) -> float:
            t = t % period
            for d, r in prof:
                if t < d:
                    return r
                t -= d
            return prof[-1][1]
        return fn, max(r for _, r in prof)
    rate = float(arrivals["rate_per_s"])
    return (lambda t: rate), rate


def draw_lengths(law: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from a length law (whole tokens, clipped to its range)."""
    kind = law["law"]
    if kind == "uniform":
        return rng.integers(int(law["min"]), int(law["max"]) + 1, size=n)
    if kind == "lognormal":
        x = np.exp(math.log(law["median"]) + law["sigma"]
                   * rng.standard_normal(n))
        return np.clip(np.ceil(x), law["min"], law["max"]).astype(np.int64)
    raise ValueError(f"unknown length law {kind!r}")


def residual_budgets(law: Dict, n: int, rng: np.random.Generator):
    """(ages, remaining budgets) of ``n`` requests caught mid-decode in a
    steady state: a length drawn with probability proportional to itself
    (the length-biased law), a uniform share of it still to go, and the
    rest already made."""
    pool = draw_lengths(law, 64 * n, rng).astype(np.float64)
    lengths = rng.choice(pool, size=n, p=pool / pool.sum()).astype(np.int64)
    left = np.maximum(np.ceil(rng.random(n) * lengths), 1).astype(np.int64)
    return lengths - left, left


def plan(mix: Dict, *, seed: int, seconds: float, vocab: int,
         max_seq: int, chunk: int, slots: int) -> List[Planned]:
    """The requests of one run: warm ones first, then the window's."""
    # one stream per part of the set, so no part depends on another's size
    pattern = [np.random.default_rng([int(mix["pattern_seed"]), k])
               for k in range(5)]
    tokens = np.random.default_rng(seed)
    rate_fn, rate_max = rate_function(mix["arrivals"])
    due = sample_arrival_times(rate_fn, float(seconds), pattern[0], rate_max)
    if len(due) > SET_SIZE:
        raise ValueError(f"{len(due)} arrivals exceed the set of {SET_SIZE}")
    prompts = draw_lengths(mix["prompt_tokens"], SET_SIZE, pattern[1])
    outputs = draw_lengths(mix["output_tokens"], SET_SIZE, pattern[2])
    warm = mix.get("warm", {})
    if warm.get("fill_slots"):
        n_warm = slots
        ages, w_outputs = residual_budgets(mix["output_tokens"], n_warm,
                                           pattern[4])
        w_prompts = draw_lengths(mix["prompt_tokens"], n_warm,
                                 pattern[3]) + ages
    else:
        n_warm = int(warm.get("requests", 0))
        w_prompts = np.resize(np.asarray(warm.get("prompt", [chunk])), n_warm)
        w_outputs = np.resize(np.asarray(warm.get("output", [2])), n_warm)
    prompts, outputs = prompts[:len(due)], outputs[:len(due)]

    def fit(prompt: int, out: int, floor: int):
        cap = max_seq - 1 - floor
        if mix.get("round_prompt_to_chunk"):
            prompt = -(-prompt // chunk) * chunk
            cap = cap // chunk * chunk
        prompt = min(prompt, cap)
        return prompt, max(min(out, max_seq - 1 - prompt), floor)

    out: List[Planned] = []
    for i in range(n_warm):
        # a warm request keeps enough budget to outlive set-up's own steps
        n, m = fit(int(w_prompts[i]), int(w_outputs[i]),
                   int(warm.get("min_budget", 1)))
        out.append(Planned(i, 0.0, tokens.integers(0, vocab, n)
                           .astype(np.int32), m, warm=True))
    for j, t in enumerate(due):
        n, m = fit(int(prompts[j]), int(outputs[j]), 1)
        out.append(Planned(n_warm + j, float(t), tokens.integers(0, vocab, n)
                           .astype(np.int32), m))
    return out
