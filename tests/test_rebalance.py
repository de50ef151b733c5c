"""Live traffic-adaptive expert rebalancing: controller invariants, the
skew-scenario throughput pin, token identity, and the check_bench gate."""

import dataclasses
import json
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import expert_server, load_balance
from repro.core.elastic import ServerPool
from repro.serving import (Autoscaler, AutoscalerConfig, EngineConfig,
                           Scenario, ServingEngine, VirtualClock, zipf_bias)

NUM_EXPERTS, NUM_SERVERS, MAX_BATCH = 16, 4, 8


def _cfg(num_experts=NUM_EXPERTS):
    # plain top-k routing: this module's Zipf trace and its pins were set
    # on it; under deepseek-r1's group-limited routing the hot set leaves
    # the second-choice experts a few hundredths of a token each, and the
    # last placement is then decided by loads whose re-plan gains less
    # than min_gain (tests/test_router_groups.py tests that routing)
    cfg = get_config("deepseek-r1").reduced()
    return cfg.replace(moe=dataclasses.replace(cfg.moe,
                                               num_experts=num_experts,
                                               n_group=1, topk_group=1))


def _engine(cfg, rebalance: bool) -> ServingEngine:
    ecfg = EngineConfig(
        mode="eaas", num_servers=NUM_SERVERS, max_batch=MAX_BATCH,
        max_seq=64, n_redundant=2,
        pool_tokens_per_client=MAX_BATCH * NUM_SERVERS,
        charge_imbalance=True,
        rebalance_interval=0.02 if rebalance else 0.0)
    clock = VirtualClock(decode_base=2e-4, decode_per_token=2e-3,
                         expert_share=0.8)
    return ServingEngine(cfg, ecfg, seed=0, clock=clock)


def _skew_scenario(vocab: int) -> Scenario:
    return (Scenario(horizon=0.5, seed=7, prompt_len=8, max_new=24,
                     vocab=vocab)
            .poisson(rate=60)
            .zipf_skew(alpha=1.2, scale=1.0))


@pytest.fixture(scope="module")
def skew_runs():
    """(frozen, rebalance, rebalance-rerun) results on one seeded
    Zipf(1.2) trace — shared across the scenario-level assertions."""
    cfg = _cfg()
    out = {}
    for name, reb in (("frozen", False), ("rebalance", True),
                      ("rerun", True)):
        eng = _engine(cfg, reb)
        res = _skew_scenario(cfg.vocab_size).run(eng)
        out[name] = (eng, res,
                     {r.request_id: tuple(r.output_tokens)
                      for r in res.requests})
    return out


# ------------------------------------------------------ prefill feedback

def test_prompt_heavy_trace_triggers_rebalance_via_prefill_feedback():
    """Chunked-prefill steps feed ``MoEStats.expert_load`` into the traffic
    EMA (the ROADMAP item): a prompt-heavy skewed trace rebalances from
    prompt traffic alone, and warms the EMA far faster than decode-only
    feedback does."""
    cfg = _cfg()

    def run(feedback: bool):
        ecfg = EngineConfig(
            mode="eaas", num_servers=NUM_SERVERS, max_batch=MAX_BATCH,
            max_seq=96, n_redundant=2, prefill_chunk=8,
            pool_tokens_per_client=MAX_BATCH * NUM_SERVERS,
            charge_imbalance=True, rebalance_interval=0.02,
            prefill_load_feedback=feedback)
        eng = ServingEngine(cfg, ecfg, seed=0, clock=VirtualClock(
            decode_base=2e-4, decode_per_token=2e-3, expert_share=0.8))
        # prompt-heavy: 48-token prompts, a single output token each --
        # nearly all router traffic happens during prefill
        sc = (Scenario(horizon=0.5, seed=7, prompt_len=48, max_new=1,
                       vocab=cfg.vocab_size)
              .poisson(rate=40).zipf_skew(alpha=1.2, scale=1.0))
        sc.run(eng)
        return eng

    fed = run(True)
    unfed = run(False)
    assert fed.metrics.rebalances >= 1
    assert fed.pool.stats.updates > 2 * unfed.pool.stats.updates
    # and the fed run actually migrated replicas toward the hot experts
    assert fed.metrics.migrated_experts > 0


# ------------------------------------------------------------ scenario pins

def test_rebalance_throughput_speedup(skew_runs):
    """The acceptance pin: under Zipf(1.2) expert traffic the live
    rebalancer sustains >= 1.3x the frozen-placement throughput."""
    _, res_f, _ = skew_runs["frozen"]
    _, res_r, _ = skew_runs["rebalance"]
    thr_f = res_f.metrics.decode_throughput
    thr_r = res_r.metrics.decode_throughput
    assert thr_r >= 1.3 * thr_f, (thr_r, thr_f)
    assert res_r.metrics.rebalances >= 1
    assert res_r.metrics.migrated_experts > 0
    assert res_r.metrics.migration_time > 0


def test_rebalance_token_streams_bitwise_identical(skew_runs):
    """Placement moves where experts run, never what they compute."""
    _, _, tok_f = skew_runs["frozen"]
    _, _, tok_r = skew_runs["rebalance"]
    assert tok_f == tok_r
    assert sum(len(t) for t in tok_f.values()) > 0


def test_rebalance_run_deterministic(skew_runs):
    """Same seed + virtual clock => identical metrics timeline, including
    migration chunks and imbalance gauges."""
    _, res_a, tok_a = skew_runs["rebalance"]
    _, res_b, tok_b = skew_runs["rerun"]
    assert tok_a == tok_b
    assert res_a.metrics.fingerprint() == res_b.metrics.fingerprint()


def test_rebalance_reduces_live_imbalance(skew_runs):
    eng_f, res_f, _ = skew_runs["frozen"]
    eng_r, res_r, _ = skew_runs["rebalance"]
    assert res_f.metrics.expert_imbalance > 1.5     # skew bites
    assert res_r.metrics.expert_imbalance < 1.3     # rebalance absorbs it
    # the hot traffic really is concentrated (the Zipf bias dominates)
    ema = eng_r.pool.stats.ema
    top2 = np.sort(ema)[-2:].sum() / ema.sum()
    assert top2 > 0.5, top2


def test_rebalance_converges_and_noops(skew_runs):
    """After the commit the live table digests equal to the planner's
    output, further evaluations are recorded as no-ops, and the controller
    is idle (nothing left to migrate)."""
    eng, res, _ = skew_runs["rebalance"]
    commits = [e for e in res.metrics.events
               if e["event"] == "rebalance_commit"]
    assert commits and all(e["converged"] for e in commits)
    assert not eng.rebalancer.migrating
    assert res.metrics.rebalance_noops > 0
    mapping, _ = eng.pool.plan()
    assert (load_balance.plan_digest(mapping, eng.pool.num_servers)
            == eng.pool.plan_digest)


def test_manual_rebalance_migrates_weights_token_identical(skew_runs):
    """The scripted one-shot ``rebalance(t)`` event moves replica weights
    together with the mapping — outputs stay bitwise identical to the
    frozen run (a stale-weight replica would corrupt expert math)."""
    _, _, tok_f = skew_runs["frozen"]
    cfg = _cfg()
    eng = _engine(cfg, rebalance=False)
    res = _skew_scenario(cfg.vocab_size).rebalance(t=0.15).run(eng)
    toks = {r.request_id: tuple(r.output_tokens) for r in res.requests}
    assert toks == tok_f
    assert res.metrics.rebalances == 1
    assert res.metrics.migrated_experts > 0
    # and the one-shot replan beats frozen placement too
    assert (res.metrics.decode_throughput
            > 1.2 * skew_runs["frozen"][1].metrics.decode_throughput)


def test_skew_events_recorded(skew_runs):
    _, res, _ = skew_runs["rebalance"]
    assert any(e["event"] == "set_skew" for e in res.metrics.events)
    assert res.applied[0]["kind"] == "set_skew"


# -------------------------------------------------------- controller units

def test_migrate_slots_matches_rebuilt_layout():
    """Incremental per-slot weight migration lands exactly the layout a
    from-scratch build of the target table would produce."""
    cfg = _cfg(num_experts=8)
    E, S = 8, 4
    bank = expert_server.init_expert_weights(jax.random.PRNGKey(0), cfg)
    red_old = np.array([[4, -1], [5, -1], [6, -1], [7, -1]], np.int32)
    red_new = np.array([[6, 5], [4, -1], [7, -1], [-1, -1]], np.int32)
    aligned, updates = load_balance.migration_updates(red_old, red_new)
    sw = expert_server.build_server_weights(bank, S, red_old)
    per = E // S
    sw = expert_server.migrate_slots(
        sw, E, [(s, per + j, new_e) for s, j, _, new_e in updates])
    want = expert_server.build_server_weights(bank, S, aligned)
    for k in sw:
        np.testing.assert_array_equal(np.asarray(sw[k]),
                                      np.asarray(want[k]))


def test_migration_updates_alignment():
    """Experts that stay on a server keep their slot (no pointless
    copies); only real occupant changes become updates."""
    old = np.array([[3, 7], [2, -1]], np.int32)
    new = np.array([[7, 3], [2, 5]], np.int32)     # same content, +5 on s1
    aligned, updates = load_balance.migration_updates(old, new)
    np.testing.assert_array_equal(aligned, [[3, 7], [2, 5]])
    assert updates == [(1, 1, -1, 5)]
    # no-change diff is empty
    _, none = load_balance.migration_updates(old, old)
    assert none == []


def test_autoscaler_defers_to_migration_in_flight():
    cfg = _cfg(num_experts=8)
    eng = _engine(cfg, rebalance=True)
    asc = Autoscaler(AutoscalerConfig(rate_per_server=1.0, min_servers=1,
                                      max_servers=8, window=0.1,
                                      cooldown=0.01))
    for t in np.linspace(0.9, 1.0, 20):
        asc.observe_arrival(float(t))       # high observed rate: wants 8
    eng.rebalancer._pending = [(0, 0, -1, 4)]
    assert asc.step(eng, t=1.0) is None     # replication first
    eng.rebalancer.abort()
    assert asc.step(eng, t=1.0) == 8        # then server-count scaling


def test_scale_to_aborts_staged_migration():
    cfg = _cfg(num_experts=8)
    eng = _engine(cfg, rebalance=True)
    eng.rebalancer._pending = [(0, 0, -1, 4)]
    eng.scale_to(2)
    assert not eng.rebalancer.migrating
    assert eng.pool.num_servers == 2
    assert eng.last_placement_change == eng.clock


# ------------------------------------------------------------- pool + plan

def test_server_pool_rebalance_skips_noop_replan():
    cfg = _cfg(num_experts=8)
    pool = ServerPool(cfg, num_servers=4, tokens_per_client=32,
                      n_redundant=2)
    load = np.ones(8)
    load[5] = 40.0
    pool.observe_load(load)
    assert pool.rebalance() is True
    smap, red = pool.smap, pool.redundant_table
    assert pool.rebalance() is False        # identical plan: no rebuild
    assert pool.smap is smap and pool.redundant_table is red


def test_plan_digest_ignores_replica_column_order():
    mapping = np.array([[0, 2, -1], [1, -1, 3]], np.int32)
    shuffled = np.array([[0, -1, 2], [1, 3, -1]], np.int32)
    other = np.array([[0, 2, -1], [1, -1, 2]], np.int32)
    d = load_balance.plan_digest(mapping, 4)
    assert d == load_balance.plan_digest(shuffled, 4)
    assert d != load_balance.plan_digest(other, 4)
    assert d != load_balance.plan_digest(mapping, 5)


def test_zipf_bias_shape_and_determinism():
    b1 = zipf_bias(16, 1.2, scale=2.0, seed=3)
    b2 = zipf_bias(16, 1.2, scale=2.0, seed=3)
    np.testing.assert_array_equal(b1, b2)
    assert b1.max() == 0.0 and b1.min() < 0.0
    np.testing.assert_array_equal(zipf_bias(16, 0.0), np.zeros(16))
    # rotation moves the hot expert
    r0 = int(np.argmax(zipf_bias(16, 1.2, seed=3)))
    r1 = int(np.argmax(zipf_bias(16, 1.2, seed=3, rotation=1)))
    assert r0 != r1


def test_shifting_hot_set_schedules_rotations():
    sc = Scenario(horizon=0.6, seed=0).shifting_hot_set(1.2, period=0.2)
    skews = [e for e in sc.events if e.kind == "set_skew"]
    assert [e.t for e in skews] == [0.0, 0.2, 0.4]
    assert [e.value[2] for e in skews] == [0, 1, 2]


def test_virtual_clock_migrate_and_imbalance_charging():
    clk = VirtualClock(decode_base=1e-3, decode_per_token=1e-3,
                       expert_share=0.5, migrate_base=1e-3,
                       migrate_per_expert=2e-3)
    assert clk.stop("migrate", tokens=3) == pytest.approx(7e-3)
    base = clk.stop("decode", tokens=8, servers=4)
    skewed = clk.stop("decode", tokens=8, servers=4, imbalance=2.0)
    assert base == pytest.approx(1e-3 + 2e-3)      # the pre-existing model
    assert skewed == pytest.approx(1e-3 + 2e-3 * 1.5)
    assert clk.stop("decode", tokens=8, servers=4, imbalance=1.0) == base


# ------------------------------------------------------------- gate (tool)

CHECK_BENCH = str(pathlib.Path(__file__).resolve().parent.parent
                  / "tools" / "check_bench.py")


def _run_gate(tmp_path, cur, base, extra=()):
    cur_p, base_p = tmp_path / "cur.json", tmp_path / "base.json"
    cur_p.write_text(json.dumps(cur))
    base_p.write_text(json.dumps(base))
    return subprocess.run(
        [sys.executable, CHECK_BENCH, "--current", str(cur_p),
         "--baseline", str(base_p), *extra],
        capture_output=True, text=True)


def _doc(fp="abc", thr=100.0):
    return {"gate": {"exact": {"token_fingerprint": fp},
                     "tolerance": {"tok_per_s": thr}}}


def test_check_bench_pass_and_tolerance(tmp_path):
    assert _run_gate(tmp_path, _doc(), _doc()).returncode == 0
    # 10% drift passes at the default 20% tolerance
    assert _run_gate(tmp_path, _doc(thr=110.0), _doc()).returncode == 0
    # 30% drift fails ...
    r = _run_gate(tmp_path, _doc(thr=130.0), _doc())
    assert r.returncode == 1 and "tok_per_s" in r.stdout
    # ... unless the tolerance is widened
    assert _run_gate(tmp_path, _doc(thr=130.0), _doc(),
                     ("--tolerance", "0.5")).returncode == 0


def test_check_bench_exact_and_missing_keys(tmp_path):
    r = _run_gate(tmp_path, _doc(fp="zzz"), _doc(fp="abc"))
    assert r.returncode == 1 and "token_fingerprint" in r.stdout
    # baseline keys missing from the current run fail; new keys pass
    cur = {"gate": {"exact": {}, "tolerance": {"tok_per_s": 100.0,
                                               "new_metric": 5.0}}}
    assert _run_gate(tmp_path, cur, _doc()).returncode == 1
    base = {"gate": {"exact": {}, "tolerance": {"tok_per_s": 100.0}}}
    cur_ok = {"gate": {"exact": {"extra": 1},
                       "tolerance": {"tok_per_s": 101.0, "more": 2.0}}}
    assert _run_gate(tmp_path, cur_ok, base).returncode == 0


def test_check_bench_gate_contract_errors(tmp_path):
    r = _run_gate(tmp_path, {"no_gate": 1}, _doc())
    assert r.returncode == 2
    missing = subprocess.run(
        [sys.executable, CHECK_BENCH, "--current",
         str(tmp_path / "nope.json"), "--baseline",
         str(tmp_path / "also_nope.json")],
        capture_output=True, text=True)
    assert missing.returncode == 2


def test_check_bench_write_baseline(tmp_path):
    cur_p = tmp_path / "cur.json"
    base_p = tmp_path / "sub" / "base.json"
    cur_p.write_text(json.dumps(_doc(thr=130.0)))
    r = subprocess.run(
        [sys.executable, CHECK_BENCH, "--current", str(cur_p),
         "--baseline", str(base_p), "--write-baseline"],
        capture_output=True, text=True)
    assert r.returncode == 0
    assert json.loads(base_p.read_text()) == _doc(thr=130.0)
