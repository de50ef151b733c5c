"""Paged KV-cache subsystem: kernel correctness, dense/paged token
identity (with and without prefix-cache hits), the deterministic TTFT win
on shared-prefix traces, and preemption liveness under an oversubscribed
pool.

Token-identity pins compare engines with the *same* chunked-prefill
setting: paged prefill always runs the chunk path, and chunk shapes must
match for bitwise-equal attention (a whole-prompt prefill computes the
same values up to matmul-shape LSBs, which MoE top-k routing can amplify
on near-ties — a pre-existing property of the chunk path, not of paging).

All engine runs sit on the virtual clock — deterministic, no wall time.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.serving import EngineConfig, Scenario, ServingEngine, VirtualClock


@pytest.fixture(scope="module")
def cfg():
    return get_config("deepseek-r1").reduced()


def _engine(cfg, **kw):
    kw.setdefault("pool_tokens_per_client", 128)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("policy", "fair")
    ecfg = EngineConfig(mode="eaas", num_servers=4, max_batch=4,
                        max_seq=128, n_redundant=2, **kw)
    return ServingEngine(cfg, ecfg, clock=VirtualClock())


def _shared_prefix_scenario(cfg, max_new=5, horizon=0.15, rate=100, seed=7):
    # two 16-token system prompts (2 blocks, 2 chunks) + unique suffixes
    return (Scenario(horizon=horizon, seed=seed, max_new=max_new,
                     vocab=cfg.vocab_size)
            .shared_prefix(n_prefixes=2, prefix_len=16, suffix_len=6)
            .poisson(rate=rate))


def _run(cfg, scenario, max_steps=20_000, **kw):
    eng = _engine(cfg, **kw)
    res = scenario.run(eng, max_steps=max_steps)
    assert res.metrics.completed == res.metrics.total_requests > 0
    return eng, res


def _tokens(res):
    return {r.request_id: tuple(r.output_tokens) for r in res.requests}


# ------------------------------------------------------------ paged kernel

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,hd,bs,mb", [
    (2, 8, 2, 32, 16, 4),
    (3, 4, 4, 64, 32, 2),
    (1, 16, 8, 16, 8, 4),
    (2, 128, 8, 64, 16, 3),       # deepseek-r1 head layout
])
def test_paged_flash_decode_vs_ref(b, h, kv, hd, bs, mb, dtype, rng):
    nb = b * mb + 1
    q = jnp.asarray(rng.normal(size=(b, h, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(nb, bs, kv, hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kv, hd)), dtype)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, nb)).reshape(b, mb), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, mb * bs + 1, size=b), jnp.int32)
    out = ops.paged_flash_decode(q, kp, vp, tables, lengths,
                                 impl="pallas_interpret")
    exp = ref.paged_flash_decode_ref(q, kp, vp, tables, lengths)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


def test_paged_ref_matches_dense_ref_on_gathered_view(rng):
    """The paged oracle is the dense oracle over the gathered view."""
    b, h, kv, hd, bs, mb = 2, 4, 2, 16, 8, 3
    nb = b * mb + 1
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, kv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kv, hd)), jnp.float32)
    tables = jnp.asarray(np.arange(1, nb).reshape(b, mb), jnp.int32)
    lengths = jnp.asarray([5, 20], jnp.int32)
    kd = kp[tables].reshape(b, mb * bs, kv, hd)
    vd = vp[tables].reshape(b, mb * bs, kv, hd)
    np.testing.assert_array_equal(
        np.asarray(ref.paged_flash_decode_ref(q, kp, vp, tables, lengths)),
        np.asarray(ref.flash_decode_ref(q, kd, vd, lengths)))


# ------------------------------------------------- dense/paged token pins

def test_paged_token_identical_no_prefix(cfg):
    """Paging alone changes where K/V lives, not what is computed: greedy
    outputs match the dense chunked engine bitwise."""
    _, rd = _run(cfg, _shared_prefix_scenario(cfg))
    _, rp = _run(cfg, _shared_prefix_scenario(cfg),
                 kv_mode="paged", kv_block_size=8, kv_prefix_cache=False)
    assert _tokens(rd) == _tokens(rp)


def test_paged_prefix_hits_token_identical_and_ttft_win(cfg):
    """Prefix-cache hits skip the shared system prompt: greedy outputs stay
    token-identical to dense while mean TTFT drops (the VirtualClock
    charges only the uncached suffix — a deterministic, benchmarkable
    win), and the hit-rate counter shows real sharing."""
    _, rd = _run(cfg, _shared_prefix_scenario(cfg))
    eng, rp = _run(cfg, _shared_prefix_scenario(cfg),
                   kv_mode="paged", kv_block_size=8)
    assert _tokens(rd) == _tokens(rp)
    m = rp.metrics
    assert m.prefix_hit_rate > 0.5
    assert m.ttft_stats()["mean"] < rd.metrics.ttft_stats()["mean"]
    kv = m.summary()["kv"]
    assert kv["prefix_hit_blocks"] > 0
    assert 0 < kv["peak_block_util"] <= 1.0


def test_paged_determinism(cfg):
    kw = dict(kv_mode="paged", kv_block_size=8)
    _, r1 = _run(cfg, _shared_prefix_scenario(cfg), **kw)
    _, r2 = _run(cfg, _shared_prefix_scenario(cfg), **kw)
    assert r1.metrics.fingerprint() == r2.metrics.fingerprint()


def test_cow_fork_on_fully_cached_prompt(cfg):
    """Identical prompts (no unique suffix): later admissions hit the whole
    prompt, fork the final shared block (copy-on-write) and recompute just
    one token — streams are identical across all requests."""
    sc = (Scenario(horizon=0.1, seed=3, max_new=6, vocab=cfg.vocab_size)
          .shared_prefix(n_prefixes=1, prefix_len=24, suffix_len=0)
          .poisson(rate=120))
    eng, res = _run(cfg, sc, kv_mode="paged", kv_block_size=8)
    m = res.metrics
    assert m.kv_cow_forks == m.total_requests - 1
    assert m.prefix_hit_rate > 0.9
    assert len({tuple(r.output_tokens) for r in res.requests}) == 1


# ------------------------------------------------ oversubscription / safety

@pytest.mark.slow
def test_preemption_keeps_engine_live_and_tokens_identical(cfg):
    """Pool squeezed to the single-request minimum: the engine admission-
    gates, preempts (release + recompute re-queue) and still completes
    every request with token streams identical to the unconstrained pool —
    no deadlock, no drops, deterministic."""
    sc = lambda: _shared_prefix_scenario(cfg, max_new=24, rate=150)
    eng, r_small = _run(cfg, sc(), kv_mode="paged", kv_block_size=8,
                        kv_num_blocks=17)
    m = r_small.metrics
    assert m.preemptions > 0
    assert m.kv_peak_block_util == pytest.approx(1.0)
    _, r_big = _run(cfg, sc(), kv_mode="paged", kv_block_size=8)
    assert r_big.metrics.preemptions == 0
    assert _tokens(r_small) == _tokens(r_big)
    # preemption delays work: the squeezed pool pays latency, not tokens
    assert m.wall_time > r_big.metrics.wall_time


@pytest.mark.slow
def test_paged_chunked_matches_paged_whole_suffix(cfg):
    """Within paged mode, chunk size is a latency knob, not a semantics
    knob: different chunkings produce identical greedy streams."""
    _, r8 = _run(cfg, _shared_prefix_scenario(cfg), kv_mode="paged",
                 kv_block_size=8, kv_prefix_cache=False, prefill_chunk=8)
    _, r4 = _run(cfg, _shared_prefix_scenario(cfg), kv_mode="paged",
                 kv_block_size=8, kv_prefix_cache=False, prefill_chunk=4)
    assert _tokens(r8) == _tokens(r4)


# ------------------------------------------------------------- validation

def test_paged_config_validation(cfg):
    with pytest.raises(ValueError, match="multiple of"):
        _engine(cfg, kv_mode="paged", kv_block_size=24)
    with pytest.raises(ValueError, match="maximal request"):
        _engine(cfg, kv_mode="paged", kv_block_size=8, kv_num_blocks=8)
    with pytest.raises(ValueError, match="lockstep"):
        _engine(cfg, kv_mode="paged", kv_block_size=8,
                decode_mode="pipelined")
