"""DeepSeek-V3's group-limited router (core/router.py) against a numpy
transcription of ``noaux_tc``: sigmoid scores, selection by score plus the
correction bias among the best groups by their top-2 sum, weights the
unbiased scores renormalised and scaled.  The bias steers which experts
are chosen and never what they weigh; without groups or bias the router
is plain top-k."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import MoEConfig
from repro.core import router

E, T, D = 32, 64, 48
CFG = MoEConfig(num_experts=E, top_k=4, d_expert=8,
                router_score_fn="sigmoid", n_group=4, topk_group=2,
                correction_bias=True, routed_scaling_factor=2.5)


def _noaux_tc(x, w, bias, cfg):
    """(ids, weights) per token, in numpy float64."""
    s = 1 / (1 + np.exp(-(x.astype(np.float64) @ w)))
    choice = s + bias
    T, E = s.shape
    per = E // cfg.n_group
    group = np.sort(choice.reshape(T, cfg.n_group, per), -1)[..., -2:].sum(-1)
    ids, weights = [], []
    for t in range(T):
        keep = np.argsort(-group[t], kind="stable")[:cfg.topk_group]
        masked = np.full(E, -np.inf)
        for g in keep:
            masked[g * per:(g + 1) * per] = choice[t, g * per:(g + 1) * per]
        top = np.argsort(-masked, kind="stable")[:cfg.top_k]
        wt = s[t, top] / s[t, top].sum() * cfg.routed_scaling_factor
        ids.append(top)
        weights.append(wt)
    return np.array(ids), np.array(weights)


def _params(seed, bias_scale):
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (T, D), jnp.float32)
    p = {"w_router": jax.random.normal(kw, (D, E), jnp.float32) * 0.3,
         "correction_bias": bias_scale * jax.random.normal(kb, (E,))}
    return x, p


def _by_id(ids, weights):
    """(ids, weights) with each token's experts in id order, so the order
    within the top-k is free."""
    ids, weights = np.asarray(ids), np.asarray(weights)
    order = np.argsort(ids, -1)
    return (np.take_along_axis(ids, order, -1),
            np.take_along_axis(weights, order, -1))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_matches_numpy_transcription(seed):
    x, p = _params(seed, 0.1)
    r = router.route(p, x, CFG)
    ids, weights = _noaux_tc(np.asarray(x), np.asarray(p["w_router"]),
                             np.asarray(p["correction_bias"], np.float64),
                             CFG)
    got_ids, got_w = _by_id(r.expert_ids, r.scores)
    want_ids, want_w = _by_id(ids, weights)
    np.testing.assert_array_equal(got_ids, want_ids)
    np.testing.assert_allclose(got_w, want_w, rtol=1e-5)
    # every choice lies in the token's topk_group groups
    groups = np.asarray(r.expert_ids) // (E // CFG.n_group)
    assert all(len(set(g)) <= CFG.topk_group for g in groups)
    np.testing.assert_allclose(np.asarray(r.scores).sum(-1), 2.5, rtol=1e-5)


def test_bias_changes_selection_not_weights():
    """A bias on one expert picks it where it was not picked; its weight
    is its unbiased score, as if chosen without the bias."""
    x, p = _params(3, 0.0)
    plain = router.route(p, x, CFG)
    probs = np.asarray(plain.full_probs)
    e = int(np.argmin(np.bincount(np.asarray(plain.expert_ids).ravel(),
                                  minlength=E)))
    bias = jnp.zeros((E,)).at[e].set(0.6)
    steered = router.route(dict(p, correction_bias=bias), x, CFG)
    ids = np.asarray(steered.expert_ids)
    picked = (ids == e).any(-1)
    assert picked.sum() > (np.asarray(plain.expert_ids) == e).any(-1).sum()
    np.testing.assert_array_equal(np.asarray(steered.full_probs), probs)
    for t in np.where(picked)[0]:
        raw = probs[t, ids[t]]
        want = raw / raw.sum() * 2.5
        np.testing.assert_allclose(np.asarray(steered.scores[t]), want,
                                   rtol=1e-5)


def test_without_groups_or_bias_is_plain_top_k():
    cfg = dataclasses.replace(CFG, n_group=1, topk_group=1,
                              correction_bias=False,
                              routed_scaling_factor=1.0)
    x, p = _params(4, 0.0)
    p = {"w_router": p["w_router"]}
    r = router.route(p, x, cfg)
    probs = jax.nn.sigmoid(x @ p["w_router"])
    scores, ids = jax.lax.top_k(probs, cfg.top_k)
    np.testing.assert_array_equal(np.asarray(r.expert_ids), np.asarray(ids))
    np.testing.assert_allclose(
        np.asarray(r.scores),
        np.asarray(scores / scores.sum(-1, keepdims=True)), rtol=1e-6)


def test_deepseek_config_routes_group_limited():
    m = get_config("deepseek-r1").moe
    assert (m.num_experts, m.top_k, m.n_group, m.topk_group) == (256, 8, 8, 4)
    assert m.correction_bias and m.routed_scaling_factor == 2.5
    chip = get_config("deepseek-r1-chip").moe
    assert (chip.num_experts, chip.router_width, chip.first_expert) == (
        16, 256, 0)
    params = router.init_router(jax.random.PRNGKey(0), 64, chip.router_width,
                                chip.correction_bias)
    assert params["w_router"].shape == (64, 256)
    assert params["correction_bias"].shape == (256,)
