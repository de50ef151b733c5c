"""An expert layer that holds a share of the routed experts (a chip under
expert parallelism): it routes over all of them, sends only the routes to
its own experts, and returns their part.  The parts of every share, with
the shared expert counted once, add up to the uncut layer; a route to an
absent expert is not a drop; the expert load and ``moe.routes_held``
count the held routes only."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_config
from repro.core import expert_server
from repro.core.moe_layer import (default_runtime, eaas_moe_apply,
                                  init_eaas_moe)
from repro.core.reference import moe_layer_ref
from repro.models.mlp import mlp
from repro.serving import (EngineConfig, Request, SamplingParams,
                           ServingEngine)

ROUTED, SHARES, SERVERS, T = 32, 4, 2, 96
HELD = ROUTED // SHARES


def _uncut():
    """The reduced deepseek-r1's MoE layer over 32 routed experts (8
    groups of 4, the best 2 kept, top-4), in float32."""
    cfg = get_config("deepseek-r1").reduced().replace(dtype="float32")
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, num_experts=ROUTED, top_k=4, n_group=8, topk_group=2))


def _share(cfg, s):
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, num_experts=HELD, routed_experts=ROUTED,
        first_expert=s * HELD))


def _apply(params, x, cfg):
    rt = default_runtime(cfg, SERVERS, T)._replace(
        capacity=T * cfg.moe.top_k)                      # dropless
    return eaas_moe_apply(params, x, cfg.moe, rt, activation="swiglu")


@pytest.fixture(scope="module")
def layer():
    cfg = _uncut()
    kp, kx, kb = jax.random.split(jax.random.PRNGKey(0), 3)
    params = init_eaas_moe(kp, cfg, SERVERS)
    # a correction bias that moves the selection
    params["router"]["correction_bias"] = 0.05 * jax.random.normal(
        kb, (ROUTED,))
    bank = {n: jnp.concatenate([w[s, :ROUTED // SERVERS]
                                for s in range(SERVERS)])
            for n, w in params["servers"].items()}            # (E, ...)
    x = jax.random.normal(kx, (T, cfg.d_model))
    return cfg, params, bank, x


def test_shares_add_up_to_the_uncut_layer(layer):
    cfg, params, bank, x = layer
    whole, st = _apply(params, x, cfg)
    assert int(st.dropped) == 0
    parts, loads = [], []
    for s in range(SHARES):
        sc = _share(cfg, s)
        held = {n: w[s * HELD:(s + 1) * HELD] for n, w in bank.items()}
        p = dict(params, servers=expert_server.build_server_weights(
            held, SERVERS, np.zeros((SERVERS, 0), np.int32)))
        y, st = _apply(p, x, sc)
        assert int(st.dropped) == 0
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(moe_layer_ref(p, x, sc.moe)),
            rtol=1e-5, atol=1e-5)
        parts.append(y)
        loads.append(np.asarray(st.expert_load))
    extra = mlp(params["shared"], x, "swiglu")          # in every share
    total = sum(parts) - (SHARES - 1) * extra
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole),
                               np.asarray(moe_layer_ref(params, x, cfg.moe)),
                               rtol=1e-5, atol=1e-5)
    # each share counts the routes to its own experts: together, all
    assert np.array_equal(np.concatenate(loads),
                          np.asarray(_apply(params, x, cfg)[1].expert_load))
    assert sum(int(l.sum()) for l in loads) == T * cfg.moe.top_k


def test_absent_routes_are_not_drops(layer):
    """A buffer far too small for every route still holds the held ones:
    the share drops nothing where the uncut layer drops."""
    cfg, params, bank, x = layer
    sc = _share(cfg, 1)
    held = {n: w[HELD:2 * HELD] for n, w in bank.items()}
    p = dict(params, servers=expert_server.build_server_weights(
        held, SERVERS, np.zeros((SERVERS, 0), np.int32)))
    y, st = _apply(p, x, sc)
    routed = int(st.expert_load.sum())
    cap = -(-routed // SERVERS) + 16
    for c, layer_cfg, prm in ((cap, sc, p), (cap, cfg, params)):
        rt = default_runtime(layer_cfg, SERVERS, T)._replace(capacity=c)
        _, st = eaas_moe_apply(prm, x, layer_cfg.moe, rt)
        held_only = layer_cfg is sc
        assert (int(st.dropped) == 0) == held_only


def test_engine_counts_routes_held():
    """Served through the engine at a 1-of-4 share, ``moe.routes_held``
    adds the pulled expert loads: about a quarter of the routes."""
    cfg = _share(_uncut().replace(dtype="bfloat16"), 0)
    eng = ServingEngine(cfg, EngineConfig(
        mode="eaas", num_servers=SERVERS, max_batch=4, max_seq=128,
        n_redundant=0, pool_tokens_per_client=64), seed=1)
    before = tracing.counters()
    rng = np.random.default_rng(2)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 24).astype(np.int32),
                    SamplingParams(temperature=0.0, max_new_tokens=6))
            for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    after = tracing.counters()
    assert all(len(r.output_tokens) == 6 for r in reqs)
    held = after["moe.routes_held"] - before.get("moe.routes_held", 0)
    assert after["moe.dropped_rows"] == before.get("moe.dropped_rows", 0)
    n_moe = cfg.num_layers - cfg.moe.first_k_dense
    # every decode step routes the slot batch; prefill routes its prompt
    routes = n_moe * cfg.moe.top_k * (4 * 24 + 4 * 5)
    assert 0 < held < 0.5 * routes
