"""Scheduler/executor engine split: semantics preservation and the
overlap/chunking performance pins (acceptance criteria of the refactor).

* pipelined and serialized two-microbatch decode produce greedy outputs
  token-identical to the lockstep (pre-split) engine on a seeded scenario;
* the overlap-aware VirtualClock puts pipelined decode throughput strictly
  above the serialized ablation;
* chunked prefill keeps the max decode gap (ITL) below the unchunked
  engine's on a bursty long-prompt trace;
* TTFT is tracked per request; per-request SamplingParams are honored.

All under the virtual clock — deterministic, no wall time.
"""

import numpy as np
import pytest

from repro.configs import get_config
from repro.serving import (Autoscaler, AutoscalerConfig, EngineConfig,
                           Request, SamplingParams, Scenario, Scheduler,
                           SchedulerConfig, ServingEngine, VirtualClock)
from repro.serving.scheduler import DecodeBatch, Idle, PrefillChunk


@pytest.fixture(scope="module")
def cfg():
    return get_config("deepseek-r1").reduced()


def _engine(cfg, **kw):
    # dispatch buffers sized for the longest prefill step so no variant
    # drops tokens — greedy outputs stay bitwise comparable across modes
    kw.setdefault("pool_tokens_per_client", 128)
    ecfg = EngineConfig(mode="eaas", num_servers=4, max_batch=4,
                        max_seq=128, n_redundant=2, **kw)
    return ServingEngine(cfg, ecfg, clock=VirtualClock())


def _run(cfg, scenario_kw=None, **engine_kw):
    sc_kw = dict(horizon=0.15, seed=7, prompt_len=8, max_new=5)
    sc_kw.update(scenario_kw or {})
    eng = _engine(cfg, **engine_kw)
    sc = Scenario(vocab=cfg.vocab_size, **sc_kw).poisson(rate=100)
    res = sc.run(eng)
    assert res.metrics.completed == res.metrics.total_requests > 0
    return eng, res


def _token_streams(res):
    return {r.request_id: tuple(r.output_tokens) for r in res.requests}


# ------------------------------------------------ semantics preservation

def test_pipelined_decode_token_identical_on_scenario(cfg):
    """The acceptance pin: pipelining changes *when* work runs, not *what*
    it computes — greedy outputs match the lockstep engine on a seeded
    scenario."""
    _, res_lock = _run(cfg, decode_mode="lockstep")
    _, res_pipe = _run(cfg, decode_mode="pipelined")
    _, res_ser = _run(cfg, decode_mode="serialized")
    assert _token_streams(res_lock) == _token_streams(res_pipe) \
        == _token_streams(res_ser)


def test_pipelined_decode_throughput_beats_serialized(cfg):
    """Same pre-submitted batch (identical step sequence across modes): the
    overlap-aware clock charges pipelined decode max(attn, expert)+ε per
    step instead of the sum, so its throughput is strictly higher."""
    def run(mode):
        eng = _engine(cfg, decode_mode=mode)
        rng = np.random.default_rng(1)
        for i in range(8):
            eng.submit(Request(
                i, rng.integers(0, cfg.vocab_size, size=8).astype(np.int32),
                SamplingParams(max_new_tokens=8)))
        return eng.run(max_steps=500)

    m_lock, m_pipe, m_ser = run("lockstep"), run("pipelined"), run("serialized")
    assert m_lock.completed == m_pipe.completed == m_ser.completed == 8
    assert m_pipe.wall_time < m_ser.wall_time
    assert m_pipe.decode_throughput > m_ser.decode_throughput
    # the split alone is free on the clock: serialized == lockstep cost
    assert m_ser.wall_time == pytest.approx(m_lock.wall_time)


def test_chunked_prefill_token_identical(cfg):
    """Chunk composition reproduces whole-prompt prefill bit-for-bit (the
    staging cache holds the same rotated keys), so greedy outputs match."""
    _, res_un = _run(cfg, scenario_kw=dict(prompt_len=12))
    _, res_ch = _run(cfg, scenario_kw=dict(prompt_len=12),
                     prefill_chunk=5, policy="fair")
    assert _token_streams(res_un) == _token_streams(res_ch)
    _, res_pp = _run(cfg, scenario_kw=dict(prompt_len=12),
                     prefill_chunk=4, policy="prefill-priority")
    assert _token_streams(res_un) == _token_streams(res_pp)


def test_determinism_with_pipeline_and_chunking(cfg):
    kw = dict(decode_mode="pipelined", prefill_chunk=4, policy="fair")
    _, r1 = _run(cfg, **kw)
    _, r2 = _run(cfg, **kw)
    assert r1.metrics.fingerprint() == r2.metrics.fingerprint()


# ------------------------------------------------------- latency pins

def test_chunked_prefill_bounds_max_itl(cfg):
    """Bursty long prompts: unchunked prefill stalls every decoding request
    for a whole prompt; fair chunking bounds the gap to one chunk."""
    def run(**kw):
        eng = _engine(cfg, **kw)
        sc = (Scenario(horizon=0.3, seed=0, prompt_len=32, max_new=8,
                       vocab=cfg.vocab_size)
              .bursty(base=20, peak=200, period=0.15, duty=0.3))
        res = sc.run(eng)
        assert res.metrics.completed == res.metrics.total_requests > 4
        return res.metrics

    m_un = run()
    m_ch = run(prefill_chunk=8, policy="fair")
    assert m_ch.itl_stats()["max"] < m_un.itl_stats()["max"]


def test_one_chunk_prompt_runs_the_chunk_program(cfg):
    """With chunking on, a prompt that fits one chunk runs the chunk
    program, which every chunk length shares, not the whole-prompt one,
    which compiles for each prompt length."""
    eng = _engine(cfg, prefill_chunk=16)
    calls = []
    for name in ("prefill", "prefill_chunk"):
        fn = getattr(eng.executor, name)

        def spy(*a, _name=name, _fn=fn, **kw):
            calls.append(_name)
            return _fn(*a, **kw)
        setattr(eng.executor, name, spy)
    for rid, n in enumerate((8, 16, 40)):
        eng.submit(Request(rid, np.arange(1, n + 1, dtype=np.int32),
                           SamplingParams(max_new_tokens=2)))
    eng.run()
    assert calls.count("prefill_chunk") == 1 + 1 + 3
    assert "prefill" not in calls


def test_ttft_tracked(cfg):
    eng, res = _run(cfg)
    m = res.metrics
    assert len(m.ttfts) == m.completed
    assert all(t > 0 for t in m.ttfts)
    st = m.ttft_stats()
    assert 0 < st["p50"] <= st["p99"] <= st["max"]
    assert "ttft" in m.summary()
    # per-request view agrees with the metric and the timeline events
    by_rid = {e["rid"]: e["ttft"] for e in m.events
              if e["event"] == "prefill"}
    for r in res.requests:
        assert r.ttft == pytest.approx(by_rid[r.request_id])
    # prefill-priority admits eagerly; fcfs batches run to completion
    # first, so arrivals wait longer for their first token
    _, res_fcfs = _run(cfg, policy="fcfs")
    assert res_fcfs.metrics.ttft_stats()["mean"] > st["mean"]


def test_set_policy_scenario_event(cfg):
    eng = _engine(cfg)
    sc = (Scenario(horizon=0.15, seed=3, max_new=4, vocab=cfg.vocab_size)
          .poisson(rate=100).set_policy(t=0.05, policy="fair"))
    res = sc.run(eng)
    assert eng.scheduler.cfg.policy == "fair"
    evs = [e for e in res.metrics.events if e["event"] == "set_policy"]
    assert evs and evs[0]["policy"] == "fair"
    assert any(a["kind"] == "set_policy" for a in res.applied)


# ---------------------------------------------------- per-request sampling

def test_per_request_sampling_params(cfg):
    """Decode honors each slot's temperature and folds the request seed in:
    greedy rows stay greedy, sampled rows are reproducible and seed-keyed."""
    def tokens(rid_temp_seed):
        eng = _engine(cfg)
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
        reqs = {rid: Request(rid, prompt.copy(),
                             SamplingParams(temperature=temp,
                                            max_new_tokens=8, seed=seed))
                for rid, temp, seed in rid_temp_seed}
        for r in reqs.values():
            eng.submit(r)
        eng.run(max_steps=200)
        assert all(r.done for r in reqs.values())
        return {rid: tuple(r.output_tokens) for rid, r in reqs.items()}

    a = tokens([(0, 0.0, 0), (1, 0.8, 1), (2, 0.8, 2)])
    b = tokens([(0, 0.0, 0), (1, 0.8, 1), (2, 0.8, 2)])
    assert a == b                      # bit-deterministic
    assert a[0] != a[1]                # sampling leaves the greedy path
    assert a[1] != a[2]                # ...and the request seed is folded in
    # same request (id, seed, prompt) ⇒ same stream regardless of the slot
    # it lands in or the batch composition around it
    c = tokens([(1, 0.8, 1), (0, 0.0, 0), (2, 0.8, 2)])
    assert c == a


# ----------------------------------------------------- scheduler unit level

def _req(i, n=10, max_new=4):
    return Request(i, np.arange(n, dtype=np.int32),
                   SamplingParams(max_new_tokens=max_new))


def test_scheduler_chunk_planning():
    s = Scheduler(SchedulerConfig(max_batch=2, prefill_chunk=4))
    s.submit(_req(0, n=10))
    plans = []
    for _ in range(3):
        p = s.next_plan()
        assert isinstance(p, PrefillChunk)
        plans.append((p.start, p.length, p.is_first, p.is_last))
        s.prefill_advanced(p.slot, p.length)
    assert plans == [(0, 4, True, False), (4, 4, False, False),
                     (8, 2, False, True)]
    assert isinstance(s.next_plan(), DecodeBatch)


def test_scheduler_policies_interleave():
    def mk(policy):
        s = Scheduler(SchedulerConfig(max_batch=2, prefill_chunk=4,
                                      policy=policy))
        # slot 0 decode-ready, slot 1 queued (8 tokens = 2 chunks)
        s.submit(_req(0, n=4))
        p = s.next_plan()
        s.prefill_advanced(p.slot, p.length)
        s.submit(_req(1, n=8))
        return s

    s = mk("prefill-priority")         # drain all chunks first
    kinds = []
    for _ in range(3):
        p = s.next_plan()
        kinds.append(type(p).__name__)
        if isinstance(p, PrefillChunk):
            s.prefill_advanced(p.slot, p.length)
    assert kinds == ["PrefillChunk", "PrefillChunk", "DecodeBatch"]

    s = mk("fair")                     # strict alternation; the setup's
    kinds = []                         # last step was a prefill, so decode
    for _ in range(4):                 # goes first
        p = s.next_plan()
        kinds.append(type(p).__name__)
        if isinstance(p, PrefillChunk):
            s.prefill_advanced(p.slot, p.length)
    assert kinds == ["DecodeBatch", "PrefillChunk", "DecodeBatch",
                     "PrefillChunk"]

    s = mk("fcfs")                     # in-flight decode precedes prefill
    assert isinstance(s.next_plan(), DecodeBatch)


def test_scheduler_backlog_and_release():
    s = Scheduler(SchedulerConfig(max_batch=1, prefill_chunk=3))
    s.submit(_req(0, n=6))
    s.submit(_req(1, n=5))             # no free slot yet
    assert s.pending_prefill_tokens() == 11
    p = s.next_plan()
    s.prefill_advanced(p.slot, p.length)
    assert s.pending_prefill_tokens() == 8
    s.prefill_advanced(p.slot, 3)      # slot 0 fully prefilled
    assert s.pending_prefill_tokens() == 5
    s.release(0)
    p = s.next_plan()                  # request 1 admitted into slot 0
    assert isinstance(p, PrefillChunk)
    assert p.request.request_id == 1 and p.length == 3


def test_scheduler_idle_when_empty():
    s = Scheduler(SchedulerConfig(max_batch=2))
    assert isinstance(s.next_plan(), Idle)


# ------------------------------------------------------- autoscaler signal

def test_autoscaler_prefill_pressure_signal():
    asc = Autoscaler(AutoscalerConfig(rate_per_server=100, min_servers=1,
                                      max_servers=8, window=0.1,
                                      prefill_tokens_per_server=64))
    for t in (0.0, 0.01, 0.02):
        asc.observe_arrival(t)
    base = asc.desired_servers(0.05, queue_depth=0, prefill_backlog=0)
    loaded = asc.desired_servers(0.05, queue_depth=0, prefill_backlog=256)
    assert loaded == min(8, base + 4)
