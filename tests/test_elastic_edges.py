"""Edge cases of the elastic expert-service tier (hypothesis-free).

ServerPool rebalance/scale liveness invariants, expert_server.serve
miss/served accounting for unhosted experts, provision()/resource_saving()
at zero and fractional rates, and the weight-resharding path behind
engine.scale_to.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.elastic import ServerPool, provision, resource_saving
from repro.core.expert_server import (ServerWeights, build_server_weights,
                                      extract_bank, make_local_table,
                                      reshard_server_weights, serve)


@pytest.fixture()
def cfg():
    return get_config("deepseek-r1").reduced()     # 8 experts


# ----------------------------------------------------------------- rebalance

def test_rebalance_preserves_liveness_mask(cfg):
    pool = ServerPool(cfg, num_servers=4, tokens_per_client=8, n_redundant=2)
    pool.server_failed(2)
    load = np.ones(cfg.moe.num_experts)
    load[0] = 50.0                                  # hot expert skew
    pool.observe_load(load)
    dead_before = pool.smap.alive.copy()
    pool.rebalance()
    np.testing.assert_array_equal(pool.smap.alive, dead_before)
    assert not pool.smap.alive[2]
    # re-plan actually replicated the hot expert
    assert (pool.smap.table[0] >= 0).sum() >= 2


def test_rebalance_without_traffic_is_noop(cfg):
    pool = ServerPool(cfg, num_servers=4, tokens_per_client=8, n_redundant=2)
    table_before = pool.smap.table.copy()
    pool.rebalance()                                # no EMA yet
    np.testing.assert_array_equal(pool.smap.table, table_before)


# ------------------------------------------------------------------ scale_to

def test_scale_to_preserves_surviving_liveness(cfg):
    pool = ServerPool(cfg, num_servers=4, tokens_per_client=8, n_redundant=1)
    pool.server_failed(1)
    pool.scale_to(8)
    assert pool.num_servers == 8
    assert not pool.smap.alive[1]                   # survivor keeps its state
    assert pool.smap.alive[[0, 2, 3, 4, 5, 6, 7]].all()  # new ranks alive
    pool.scale_to(2)
    assert pool.num_servers == 2
    assert pool.smap.alive[0] and not pool.smap.alive[1]


def test_scale_to_rejects_non_divisor(cfg):
    pool = ServerPool(cfg, num_servers=4, tokens_per_client=8)
    with pytest.raises(ValueError, match="feasible"):
        pool.scale_to(3)                            # 8 experts % 3 != 0
    assert pool.feasible_counts() == [1, 2, 4, 8]


def test_scale_to_mapping_local_table_coherent(cfg):
    """After a resize every mapped replica actually hosts the expert
    (the miss == 0 property)."""
    pool = ServerPool(cfg, num_servers=4, tokens_per_client=8, n_redundant=2)
    pool.observe_load(np.arange(cfg.moe.num_experts, dtype=float) + 1)
    pool.scale_to(2)
    E = cfg.moe.num_experts
    local = make_local_table(E, pool.num_servers, pool.redundant_table)
    for e in range(E):
        for s in pool.smap.table[e][pool.smap.table[e] >= 0]:
            assert local[s, e] >= 0, (e, s)


def test_reshard_roundtrips_weight_bank():
    rng = np.random.default_rng(0)
    E, d, f = 8, 4, 6
    bank = {k: jnp.asarray(rng.normal(size=(E, d, f)).astype(np.float32))
            for k in ("w_gate", "w_up", "w_down")}
    red4 = np.array([[1], [3], [5], [7]], np.int32)
    sw4 = build_server_weights(bank, 4, red4)
    # bank recovery from the primary slots is exact
    bank_rt = extract_bank(sw4, E)
    for k in bank:
        np.testing.assert_array_equal(np.asarray(bank_rt[k]),
                                      np.asarray(bank[k]))
    # reshard 4 -> 2 matches building from the bank directly
    red2 = np.array([[6], [0]], np.int32)
    sw2 = reshard_server_weights(sw4, E, 2, red2)
    expect = build_server_weights(bank, 2, red2)
    for k in bank:
        np.testing.assert_array_equal(np.asarray(sw2[k]),
                                      np.asarray(expect[k]))
    # and a stacked leading (layer) dim passes through untouched
    sw4_l = {k: jnp.stack([v, v]) for k, v in sw4.items()}
    sw2_l = reshard_server_weights(sw4_l, E, 2, red2)
    for k in bank:
        assert sw2_l[k].shape == (2,) + expect[k].shape
        np.testing.assert_array_equal(np.asarray(sw2_l[k][1]),
                                      np.asarray(expect[k]))


# ------------------------------------------------------- serve miss accounting

def test_serve_counts_miss_for_unhosted_expert():
    rng = np.random.default_rng(0)
    E, L, d, f, C = 4, 2, 8, 16, 4
    # this server hosts experts {0, 3} in slots {0, 1}
    local = jnp.asarray(np.array([0, -1, -1, 1], np.int32))
    w = ServerWeights(
        w_gate=jnp.asarray(rng.normal(size=(1, L, d, f)).astype(np.float32)),
        w_up=jnp.asarray(rng.normal(size=(1, L, d, f)).astype(np.float32)),
        w_down=jnp.asarray(rng.normal(size=(1, L, f, d)).astype(np.float32)),
        local_table=local[None])
    tokens = jnp.asarray(rng.normal(size=(1, 1, C, d)).astype(np.float32))
    eids = jnp.asarray(np.array([[[0, 2, 3, 0]]], np.int32))  # 2 unhosted
    scores = jnp.ones((1, 1, C), jnp.float32)
    counts = jnp.asarray(np.array([[3]], np.int32))          # last invalid

    out, stats = serve(tokens, eids, scores, counts, w, impl="xla_ragged")
    assert int(stats.miss) == 1                     # the expert-2 token
    assert int(stats.served) == 2                   # experts 0 and 3
    out = np.asarray(out)[0]
    assert np.any(out[0, 0] != 0) and np.any(out[0, 2] != 0)
    np.testing.assert_array_equal(out[0, 1], 0)     # miss row zeroed
    np.testing.assert_array_equal(out[0, 3], 0)     # invalid row zeroed


def test_serve_all_hosted_no_miss():
    rng = np.random.default_rng(1)
    E, S, d = 4, 2, 8
    bank = {k: jnp.asarray(rng.normal(size=(E, d, d)).astype(np.float32))
            for k in ("w_gate", "w_up", "w_down")}
    sw = build_server_weights(bank, S, np.zeros((S, 0), np.int32))
    local = make_local_table(E, S, np.zeros((S, 0), np.int32))
    w = ServerWeights(sw["w_gate"][:1], sw["w_up"][:1], sw["w_down"][:1],
                      jnp.asarray(local[:1]))
    tokens = jnp.asarray(rng.normal(size=(1, 1, 2, d)).astype(np.float32))
    eids = jnp.asarray(np.array([[[0, 1]]], np.int32))
    _, stats = serve(tokens, eids, jnp.ones((1, 1, 2)), jnp.asarray([[2]]),
                     w, impl="xla_ragged")
    assert int(stats.miss) == 0 and int(stats.served) == 2


# --------------------------------------------------- provision edge behaviour

def test_provision_zero_and_fractional_rates():
    assert provision(0.0, 10.0) == 1                # never provision zero
    assert provision(-5.0, 10.0) == 1
    assert provision(0.1, 10.0) == 1                # fractional need ceils
    assert provision(10.1, 10.0) == 2
    # degenerate per-server rate: the 1e-9 guard yields a finite (huge)
    # demand instead of a ZeroDivisionError
    assert provision(0.5, 0.0) >= 1
    assert provision(5.0, 1.0, granularity=4) == 8  # group rounding
    assert provision(8.0, 1.0, granularity=4) == 8


def test_resource_saving_zero_and_fractional():
    # zero traffic: EAAS keeps 1, monolithic keeps one whole group
    assert resource_saving(0.0, 10.0, monolithic_group=8) == pytest.approx(
        1 - 1 / 8)
    # the paper's 37.5%: 5120 req/s at 128 req/s/server vs a 64-group
    assert resource_saving(5120, 8192 / 64, 64) == pytest.approx(0.375)
    # fractional rate just under one server of traffic
    assert resource_saving(0.9 * 10, 10.0, 4) == pytest.approx(1 - 1 / 4)
    # when fine == coarse there is nothing to save
    assert resource_saving(32.0, 1.0, 4) == pytest.approx(0.0)
