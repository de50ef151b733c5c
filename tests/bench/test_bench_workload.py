"""The traffic generator: a seed fixes the traffic, and every seed offers
the same set of sizes and arrivals in another order."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import workload

MIXES = Path(__file__).resolve().parents[2] / "bench" / "traffic"


def _plan(name, seed, seconds=20.0):
    mix = json.loads((MIXES / f"{name}.json").read_text())
    return mix, workload.plan(mix, seed=seed, seconds=seconds, vocab=32000,
                              max_seq=2048, chunk=128, slots=32)


@pytest.mark.parametrize("name", ["reason", "chat"])
def test_same_seed_same_traffic(name):
    _, a = _plan(name, 2 ** 31 + 77)
    _, b = _plan(name, 2 ** 31 + 77)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.rid, x.due, x.max_new, x.warm) == (y.rid, y.due, y.max_new,
                                                     y.warm)
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", ["reason", "chat"])
def test_other_seed_same_work_other_tokens(name):
    _, a = _plan(name, 5)
    _, b = _plan(name, 2 ** 31 + 6)
    assert [(p.due, len(p.prompt), p.max_new, p.warm) for p in a] == [
        (p.due, len(p.prompt), p.max_new, p.warm) for p in b]
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_reason_mix_shape():
    mix, ps = _plan("reason", 3)
    warm = [p for p in ps if p.warm]
    assert len(warm) == 32 and all(p.due == 0 for p in warm)
    assert all(p.max_new >= mix["warm"]["min_budget"] for p in warm)
    for p in ps:
        assert len(p.prompt) % 128 == 0 and len(p.prompt) >= 128
        assert len(p.prompt) + p.max_new <= 2047
        assert p.prompt.min() >= 0 and p.prompt.max() < 32000
    offered = [p for p in ps if not p.warm]
    assert all(len(p.prompt) <= 512 for p in offered)
    # a warm request's prompt holds the tokens it made before the window:
    # steady-state contexts, here a mean of several hundred tokens more
    # than a prompt alone
    assert (np.mean([len(p.prompt) for p in warm])
            > np.mean([len(p.prompt) for p in offered]) + 300)
    assert all(1024 <= p.max_new <= 1536 for p in offered
               if len(p.prompt) + 1536 <= 2047)
    # 2.3 requests/s over 20 s
    assert 25 <= len(offered) <= 70


def test_residual_budgets_follow_the_length_biased_law():
    rng = np.random.default_rng(0)
    law = {"law": "uniform", "min": 1024, "max": 1536}
    age, r = workload.residual_budgets(law, 20000, rng)
    assert r.min() >= 1 and r.max() <= 1536 and age.min() >= 0
    assert 1024 <= (age + r).min() and (age + r).max() <= 1536
    # E[L^2] / (2 E[L]) for L uniform on [1024, 1536], for what is left
    # and for what was made
    L = np.arange(1024, 1537, dtype=np.float64)
    assert abs(r.mean() - (L ** 2).mean() / (2 * L.mean())) < 15
    assert abs(age.mean() - (L ** 2).mean() / (2 * L.mean())) < 15


def test_rate_profile_thins_to_the_profile():
    rng = np.random.default_rng(1)
    fn, peak = workload.rate_function({"rate_profile": [[1.0, 10.0],
                                                        [3.0, 0.0]]})
    t = workload.sample_arrival_times(fn, 400.0, rng, peak)
    assert np.all(t % 4.0 < 1.0)
    assert abs(len(t) / 100 - 10.0) < 1.5
