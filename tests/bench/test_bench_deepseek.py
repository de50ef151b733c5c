"""The DeepSeek-V3 family (``bench/models/deepseek_v3.py``) at a tiny size
on the CPU: the served path (chunked prefill, then decode through the
latent cache, via the benchmark's ``Cluster``) against the family's
float32 reference, for a share of the routed experts at the start and in
the middle of the router's width; a whole run of the harness, correct,
with its fp8 control not; the MLA readers by hand; and the sizes of the
``deepseek-r1.ep16`` cut."""

import copy

import numpy as np
import pytest

from bench import run, system
from bench.drive import Record, Step
from bench.models import deepseek_v3 as ds, family, load_config
from bench_tiny import PEAKS, tiny_run

SEED = 2 ** 31 + 9
# bf16 weights and activations against float32: relative L2 of the logits
# (the chip's limit; a near-tie in routing flips an expert at a few
# positions, so the bound is on the 90th percentile)
REL_TOL = 3e-2


def tiny_deepseek(first_held: int = 0) -> dict:
    """deepseek-r1.ep16 at tiny widths: 8 of 32 routed experts held (8
    groups of 4, the best 2 kept, top-4), one dense layer and two MoE
    layers, buffers sized dropless (a 32-token chunk sends a server at
    most 2 experts x 32 rows; it holds 80)."""
    cfg = copy.deepcopy(load_config("deepseek-r1.ep16"))
    cfg.update(hidden_size=128, intermediate_size=256,
               moe_intermediate_size=64, num_attention_heads=4,
               num_key_value_heads=4, q_lora_rank=64, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               n_routed_experts=8, n_group=8, topk_group=2,
               num_experts_per_tok=4, num_hidden_layers=3,
               first_k_dense_replace=1, vocab_size=512)
    cfg["expert_share"] = {"routed_experts": 32, "first_held": first_held,
                           "chips": 4}
    cfg["serving"] = dict(cfg["serving"], max_batch=4, max_seq=512,
                          prefill_chunk=32, pool_tokens_per_client=64)
    return cfg


@pytest.fixture(scope="module", params=[0, 8], ids=["held_0", "held_8"])
def served(request):
    cfg = tiny_deepseek(request.param)
    a = ds.arch(cfg)
    cluster, eng, _, _ = system.build(cfg, a, SEED)
    return a, eng.executor


def _rel_rows(x, y):
    return np.linalg.norm(x - y, axis=-1) / np.linalg.norm(y, axis=-1)


def test_prefill_then_decode_match_the_reference(served):
    a, ex = served
    rng = np.random.default_rng(4)
    B = ex.max_batch
    prompts = [rng.integers(0, a.vocab, n).astype(np.int32)
               for n in (64, 96, 32, 64)]
    toks, errs = [], []
    for b, p in enumerate(prompts):
        for s in range(0, len(p), 32):
            lg, load = ex.prefill_chunk(b, p[s:s + 32], s, is_first=s == 0,
                                        is_last=s + 32 >= len(p))
        first = np.asarray(lg)[0, :a.vocab]
        toks.append([int(first.argmax())])
        ref = ds.forward(a, SEED, [p], [np.array([len(p) - 1])])[0][0]
        errs.extend(_rel_rows(first[None], ref[None]))
    steps, held = 4, 0
    got = [[] for _ in range(B)]
    for _ in range(steps):
        t = np.asarray([[tk[-1]] for tk in toks], np.int32)
        lg, load = ex.decode(t)
        lg = np.asarray(lg)[:, :a.vocab]
        held += int(np.asarray(load).sum())
        for b in range(B):
            got[b].append(lg[b])
            toks[b].append(int(lg[b].argmax()))
    # only the routes to the 8 held of 32 experts are served
    assert 0 < held < steps * B * a.top_k * a.moe_layers
    seqs = [np.concatenate([p, np.asarray(tk[:-1], np.int32)])
            for p, tk in zip(prompts, toks)]
    reads = [np.arange(len(p), len(p) + steps) for p in prompts]
    ref = ds.forward(a, SEED, seqs, reads)
    for b in range(B):
        errs.extend(_rel_rows(np.stack(got[b]), ref[b]))
    assert np.percentile(errs, 90) < REL_TOL


def test_a_whole_run_is_correct_and_its_fp8_control_is_not():
    out = tiny_run("reason", seed=2 ** 31 + 91, cfg=tiny_deepseek(),
                   controls=("fp8",))
    assert out["correct"] is True, out["checked"]
    fp8 = out["controls"]["fp8"]
    assert fp8["correct"] is False
    name = "logit_err_p90"
    assert fp8["readings"][name] > 3 * out["readings"][name]


def _ctx(a, seconds):
    """Three decode steps of two requests (prompts of 128 and 256), a
    chunk step, and an idle step; ``seconds`` of ``mla``-tagged ops."""
    rec = Record(t0=0.0, t1=10.0)
    rec.steps = [Step(1.0, 2.0, "decode"), Step(2.0, 3.0, "decode"),
                 Step(3.0, 4.0, "prefill",
                      plan=type("Plan", (), {"start": 128, "length": 128})),
                 Step(4.0, 5.0, "decode"), Step(5.0, 5.5)]
    rec.prompt_len = {0: 128, 1: 256}
    # token 0 from prefill (before the window), then one a decode step
    rec.token_times = {0: [0.5, 2.0, 3.0, 5.0], 1: [0.5, 2.0, 5.0]}
    reduced = {"op_seconds": {"%a": seconds, "%b": 1.0},
               "op_text": {"%a": '%a = f32[1] fusion(), frontend_attributes'
                                 '={eaas_stage="mla"}',
                           "%b": '%b = f32[1] fusion()'}}
    return run.Context(rec, a, PEAKS, reduced, ds)


def test_mla_readers_by_hand():
    a = ds.arch(load_config("deepseek-r1.ep16"))
    ctx = _ctx(a, 2.0)
    assert run.read_metric("mla_ms", ctx) == pytest.approx(2e3 / 4)
    steps = [((129, 257), ()), ((130,), ()), ((), ((128, 128),)),
             ((131, 258), ())]
    least = 0.0
    for keys, chunks in steps:
        ops, nbytes = ds.mla_work(a, keys=keys, chunks=chunks)
        least += max(ops / PEAKS["bf16_flops_per_s"],
                     nbytes / PEAKS["hbm_bytes_per_s"])
    assert run.read_metric("mla_roofline", ctx) == pytest.approx(
        100 * least / 2.0)
    # a family without mla_work, or no tagged op, reads nothing
    assert run.read_metric("mla_roofline", run.Context(
        ctx.rec, a, PEAKS, ctx.trace, object())) is None
    ctx.trace["op_text"]["%a"] = "%a = f32[1] fusion()"
    assert run.read_metric("mla_ms", ctx) is None
    assert run.read_metric("mla_roofline", ctx) is None


def test_mla_work_by_hand():
    """At the published widths: 374 MB of MLA weights a layer once a step;
    576 cached values (1152 B) a position; the absorbed query's work."""
    a = ds.arch(load_config("deepseek-r1.ep16"))
    ops, nbytes = ds.mla_work(a, keys=[1000])
    proj = 2 * (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
                + 128 * 128 * 7168)
    core = 2 * 128 * (128 * 512 + 576 * 1000 + 512 * 1000 + 512 * 128)
    assert ops == 7 * (proj + core)
    weights = 2 * (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
                   + 512 * 128 * 256 + 128 * 128 * 7168)
    assert weights == pytest.approx(374e6, rel=0.01)
    assert nbytes == 7 * (weights + 1152 * 1000)
    # a chunk's queries attend causally; its latent is read once
    ops_c, bytes_c = ds.mla_work(a, chunks=[(0, 2)])
    assert ops_c == 7 * (2 * proj + sum(
        2 * 128 * (128 * 512 + 1088 * k + 512 * 128) for k in (1, 2)))
    assert bytes_c == 7 * (weights + 1152 * 2)
    assert ds.mla_work(a) == (0.0, 0.0)


def test_ep16_cut_sizes():
    cfg = load_config("deepseek-r1.ep16")
    a = family(cfg).arch(cfg)
    assert (a.num_experts, a.routed_experts, a.first_held) == (16, 256, 0)
    assert (a.num_layers, a.first_k_dense, a.moe_layers) == (7, 3, 4)
    assert (a.d_ff, a.d_expert, a.top_k, a.vocab) == (18432, 2048, 8, 16160)
    mcfg = ds.program_config(cfg, a)
    assert mcfg.num_params() * 2 / 1e9 == pytest.approx(11.5, abs=0.1)
    assert mcfg.moe.router_width == 256 and mcfg.mla.kv_lora_rank == 512


def test_family_yarn_is_the_programs():
    from repro.models.rope import yarn_freqs

    cfg = load_config("deepseek-r1.ep16")
    a = ds.arch(cfg)
    mcfg = ds.program_config(cfg, a)
    np.testing.assert_allclose(
        np.asarray(yarn_freqs(64, a.rope_theta, mcfg.rope_scaling)),
        ds.yarn_inv_freq(a), rtol=1e-6)


@pytest.mark.parametrize("change, message", [
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"n_routed_experts": 12}, "do not hold"),
], ids=["softmax", "greedy", "uneven_share"])
def test_family_refuses_what_it_cannot_compute(change, message):
    with pytest.raises(ValueError, match=message):
        ds.arch(dict(load_config("deepseek-r1.ep16"), **change))
