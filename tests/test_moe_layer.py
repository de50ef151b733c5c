"""EaasMoELayer end-to-end: vs a direct dense-MoE oracle, replication
invariance, failover correctness, monolithic equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import expert_server, moe_layer as eaas
from repro.core.monolithic import monolithic_ep_apply, monolithic_runtime
from repro.core import load_balance, mapping as emap


def _setup(S=4, n_red=0, seed=0, redundant_table=None):
    cfg = get_config("kimi-k2-1t-a32b").reduced()   # 8 experts top-2 +shared
    key = jax.random.PRNGKey(seed)
    params = eaas.init_eaas_moe(key, cfg, S, redundant_table=redundant_table)
    T = 24
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (T, cfg.d_model), jnp.float32) * 0.3
    rt = eaas.default_runtime(cfg, S, T, redundant_table=redundant_table)
    rt = rt._replace(capacity=T * cfg.moe.top_k, gemm_impl="xla_ragged")
    return cfg, params, x, rt


def _dense_oracle(cfg, params, x):
    """sum_k score_k · expert_k(x) + shared — no dispatch machinery."""
    from repro.core import router
    from repro.models.mlp import mlp

    m = cfg.moe
    r = router.route(params["router"], x, m)
    # reassemble the global expert bank from per-server primaries
    S, L = params["servers"]["w_gate"].shape[:2]
    per = m.num_experts // S
    wg = params["servers"]["w_gate"][:, :per].reshape(m.num_experts, *params["servers"]["w_gate"].shape[2:])
    wu = params["servers"]["w_up"][:, :per].reshape(m.num_experts, *params["servers"]["w_up"].shape[2:])
    wd = params["servers"]["w_down"][:, :per].reshape(m.num_experts, *params["servers"]["w_down"].shape[2:])
    out = jnp.zeros_like(x)
    for t in range(x.shape[0]):
        acc = jnp.zeros((x.shape[1],), jnp.float32)
        for j in range(m.top_k):
            e = int(r.expert_ids[t, j])
            h = jax.nn.silu(x[t] @ wg[e]) * (x[t] @ wu[e])
            acc = acc + r.scores[t, j] * (h @ wd[e])
        out = out.at[t].set(acc.astype(x.dtype))
    if "shared" in params:
        out = out + mlp(params["shared"], x, cfg.activation)
    return out


# pallas_interpret: the grouped-GEMM kernel over every logical server's
# experts, as "auto" runs it on TPU
@pytest.mark.parametrize("gemm_impl", ["xla_ragged", "pallas_interpret"])
def test_eaas_matches_dense_oracle(gemm_impl):
    cfg, params, x, rt = _setup(S=4)
    rt = rt._replace(gemm_impl=gemm_impl)
    y, stats = eaas.eaas_moe_apply(params, x, cfg.moe, rt,
                                   activation=cfg.activation)
    y_ref = _dense_oracle(cfg, params, x)
    assert int(stats.dropped) == 0 and int(stats.miss) == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


def test_replicas_do_not_change_output():
    """Replicated experts are bit-equivalent services: adding replicas (and
    spreading traffic over them) must not change the math."""
    cfg, params0, x, rt0 = _setup(S=4)
    y0, _ = eaas.eaas_moe_apply(params0, x, cfg.moe, rt0,
                                activation=cfg.activation)
    E, S = cfg.moe.num_experts, 4
    mapping, red = load_balance.eplb_plan(np.ones(E), S, n_redundant=2)
    cfg2, params2, x2, rt2 = _setup(S=4, redundant_table=red)
    # copy the SAME bank weights into the replicated layout
    for k in ("w_gate", "w_up", "w_down"):
        per = E // S
        bank = params0["servers"][k][:, :per].reshape(
            E, *params0["servers"][k].shape[2:])
        params2["servers"][k] = expert_server.build_server_weights(
            {"w_gate": bank, "w_up": bank, "w_down": bank}, S, red)[k]
    params2["router"] = params0["router"]
    if "shared" in params0:
        params2["shared"] = params0["shared"]
    rt2 = rt2._replace(mapping=jnp.asarray(mapping))
    y2, st2 = eaas.eaas_moe_apply(params2, x, cfg.moe, rt2,
                                  activation=cfg.activation)
    assert int(st2.miss) == 0
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)


def test_failover_preserves_output():
    """Killing a server whose experts all have live replicas must leave the
    output unchanged (transparent failover, paper §3.4)."""
    E, S = 8, 4
    # give EVERY expert a replica on (primary+1) % S
    mapping = emap.default_mapping(E, S, max_replicas=2)
    red = np.zeros((S, 2), np.int32) - 1
    per = E // S
    for e in range(E):
        p = mapping[e, 0]
        q = (p + 1) % S
        slot = np.argmax(red[q] < 0)
        red[q, slot] = e
        mapping[e, 1] = q
    cfg, params, x, rt = _setup(S=S, redundant_table=red)
    rt = rt._replace(mapping=jnp.asarray(mapping))
    y_before, st_b = eaas.eaas_moe_apply(params, x, cfg.moe, rt,
                                         activation=cfg.activation)
    rt_dead = rt._replace(alive=rt.alive.at[2].set(False))
    y_after, st_a = eaas.eaas_moe_apply(params, x, cfg.moe, rt_dead,
                                        activation=cfg.activation)
    assert int(st_a.miss) == 0
    np.testing.assert_allclose(np.asarray(y_before), np.asarray(y_after),
                               rtol=2e-4, atol=2e-4)


def test_monolithic_ep_equivalent_when_healthy():
    """EAAS degenerates exactly to monolithic EP with a primary-only map."""
    cfg, params, x, rt = _setup(S=4)
    y_eaas, _ = eaas.eaas_moe_apply(params, x, cfg.moe, rt,
                                    activation=cfg.activation)
    rt_mono = monolithic_runtime(cfg, 4, x.shape[0], "xla_ragged")
    rt_mono = rt_mono._replace(capacity=rt.capacity)
    y_mono, _ = monolithic_ep_apply(params, x, cfg, rt_mono)
    np.testing.assert_allclose(np.asarray(y_eaas), np.asarray(y_mono),
                               rtol=1e-5, atol=1e-5)


def test_miss_counted_on_inconsistent_mapping():
    """Routing to a server that does not host the expert is counted."""
    cfg, params, x, rt = _setup(S=4)
    bad = rt.mapping.at[:, 0].set((rt.mapping[:, 0] + 1) % 4)
    rt_bad = rt._replace(mapping=bad)
    _, stats = eaas.eaas_moe_apply(params, x, cfg.moe, rt_bad,
                                   activation=cfg.activation)
    assert int(stats.miss) == x.shape[0] * cfg.moe.top_k


def test_plain_reference_matches_dense_oracle():
    """The vectorised float32 reference the chip smoke compares against
    agrees with the per-token oracle here."""
    from repro.core.reference import moe_layer_ref

    cfg, params, x, _ = _setup(S=4)
    np.testing.assert_allclose(
        np.asarray(moe_layer_ref(params, x, cfg.moe, cfg.activation)),
        np.asarray(_dense_oracle(cfg, params, x), np.float32),
        rtol=2e-4, atol=2e-4)


# ------------------------------------------- servers folded into the groups

def _one_server(tokens, expert_ids, scores, counts, w_gate, w_up, w_down,
                local_table, impl):
    """One server's batch, as each server ran it before the servers were
    folded into one grouped GEMM: its rows sorted by its own slots, a
    grouped GEMM over its L experts.  tokens (Clients, C, d)."""
    from repro.kernels import ops as kops

    Sc, C, d = tokens.shape
    M = Sc * C
    x = tokens.reshape(M, d)
    eid = expert_ids.reshape(M)
    sc = scores.reshape(M)
    valid = (jnp.arange(C)[None, :] < counts[:, None]).reshape(M)
    valid &= eid >= 0
    L = w_gate.shape[0]
    slot = jnp.where(valid, local_table[jnp.clip(eid, 0)], L)
    hosted = slot >= 0
    miss = jnp.sum(valid & ~hosted)
    slot = jnp.where(hosted, slot, L)
    order = jnp.argsort(slot)
    xs = x[order]
    group_sizes = jnp.bincount(slot, length=L + 1)[:L]
    ecap = max(8, ((-(-(M * 5) // (4 * L))) + 7) // 8 * 8)
    gg = lambda a, w: kops.grouped_gemm_counted(a, w, group_sizes, impl=impl,
                                                expert_capacity=ecap)
    h_gate, t_gate = gg(xs, w_gate)
    h_up, t_up = gg(xs, w_up)
    h = jax.nn.silu(h_gate.astype(jnp.float32)).astype(h_up.dtype) * h_up
    y, t_down = gg(h, w_down)
    y = y.astype(jnp.float32) * sc[order][:, None]
    in_group = jnp.arange(M) < jnp.sum(group_sizes)
    y = jnp.where((valid[order] & hosted[order] & in_group)[:, None], y, 0)
    out = jnp.zeros((M, d), jnp.float32).at[order].set(y)
    out = out.reshape(Sc, C, d).astype(tokens.dtype)
    return out, expert_server.ServeStats(
        miss=miss, served=jnp.sum(valid & hosted),
        tiles_live=t_gate.live + t_up.live + t_down.live,
        tiles=t_gate.total + t_up.total + t_down.total)


def _per_server_serve(tokens, expert_ids, scores, counts, weights, *,
                      impl="auto", layer=None):
    """The oracle: the per-server path the folded ``serve`` replaced, the
    S logical servers vmapped (a given layer sliced out first)."""
    wg, wu, wd, table = weights
    if layer is not None:
        wg, wu, wd = wg[layer], wu[layer], wd[layer]
    out, st = jax.vmap(lambda *a: _one_server(*a, impl=impl))(
        tokens, expert_ids, scores, counts, wg, wu, wd, table)
    return out, jax.tree.map(jnp.sum, st)


def _failover_setup():
    """4 servers of 8 experts, one redundant slot each (server 2's empty),
    server 1 dead: expert 2 fails over to its replica on server 0, expert 3
    has none and falls back to server 0, which does not host it (a miss).
    Buffers of 128 rows a server, whole row tiles."""
    E, S = 8, 4
    red = np.array([[2], [0], [-1], [5]], np.int32)
    mapping = emap.default_mapping(E, S, max_replicas=2)
    mapping[:, 1] = -1
    for s, e in ((0, 2), (1, 0), (3, 5)):
        mapping[e, 1] = s
    cfg, params, x, rt = _setup(S=S, redundant_table=red)
    rt = rt._replace(mapping=jnp.asarray(mapping),
                     alive=rt.alive.at[1].set(False), capacity=128)
    return cfg, params, x, rt


def _stacked(params, layers=3):
    """params with a bank of ``layers`` layers whose experts differ."""
    banks = {k: jnp.stack([v * (1.0 + 0.5 * i) for i in range(layers)])
             for k, v in params["servers"].items()}
    return dict(params, servers=banks)


def _apply(params, x, cfg, rt, layer=None):
    fn = jax.jit(lambda p, v, lay: eaas.eaas_moe_apply(
        p, v, cfg.moe, rt, activation=cfg.activation, layer=lay))
    return fn(params, x, layer)


@pytest.mark.parametrize("gemm_impl", ["pallas_interpret", "xla_ragged"])
@pytest.mark.parametrize("layer", [None, 2], ids=["bank", "stacked_layer2"])
def test_folded_serve_matches_per_server_path(monkeypatch, gemm_impl,
                                              layer):
    """One grouped GEMM over every server's experts (group server·L + slot,
    read at layer·S·L from a stacked bank) gives the per-server path's
    outputs bit for bit, and the same misses, drops and row tiles, with
    redundant slots, an empty slot, a dead server and routes to a server
    that does not host the expert."""
    cfg, params, x, rt = _failover_setup()
    rt = rt._replace(gemm_impl=gemm_impl)
    if layer is not None:
        params = _stacked(params)
        layer = jnp.int32(layer)
    y, st = _apply(params, x, cfg, rt, layer)
    monkeypatch.setattr(expert_server, "serve", _per_server_serve)
    y_ref, st_ref = _apply(params, x, cfg, rt, layer)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    for name in ("miss", "dropped", "gemm_tiles_live", "gemm_tiles"):
        assert int(getattr(st, name)) == int(getattr(st_ref, name)), name
    assert int(st.miss) > 0 and int(st.dropped) == 0
    if gemm_impl == "pallas_interpret":
        assert 0 < int(st.gemm_tiles_live) < int(st.gemm_tiles)
    if layer is not None:    # another layer's experts give another result
        y_other, _ = _apply(params, x, cfg, rt, jnp.int32(1))
        assert not np.array_equal(np.asarray(y), np.asarray(y_other))
