"""The readers of what the program records about itself: device time by
stage tag, from a reduced trace worked by hand, and host time per engine
step, from spans planted in ``repro.tracing``'s ring."""

import numpy as np
import pytest

from bench import run, stages
from bench.drive import Record, Step
from bench.models import mixtral
from bench.trace import TEXT_CHARS
from repro import tracing

# op texts as the profiler gives them on the chip: the grouped GEMM kernel
# with its own tag inside the server stage's, the server's weight-slot
# copy, the other stages, a layer-bank slice XLA made outside any stage,
# and an op whose text is cut before its tag
OPS = {
    "%gemm": (
        '%closed_call.40 = f32[512,14336]{1,0:T(8,128)S(1)} custom-call('
        's32[8]{0:T(128)S(1)} %fusion.566, s32[8]{0:T(128)S(1)} '
        '%fusion.567, bf16[512,4096]{1,0:T(8,128)(2,1)S(1)} %fusion.23, '
        'bf16[2,4096,14336]{2,1,0:T(8,128)(2,1)} '
        '%dynamic-slice_bitcast_fusion.28), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{s32[8]{0}, s32[8]{0}, bf16[512,4096]{1,0}, '
        'bf16[2,4096,14336]{2,1,0}}, frontend_attributes={eaas_stage='
        '"server",kernel_metadata={\n"eaas_stage":"gemm"\n}}'),
    "%slots": (
        '%dynamic-slice_bitcast_fusion.28 = bf16[2,4096,14336]{2,1,0} '
        'fusion(bf16[4,2,4096,14336]{3,2,1,0} %bank, s32[] %i), '
        'kind=kLoop, calls=%fused_computation.3, '
        'frontend_attributes={eaas_stage="server"}'),
    "%router": '%fusion.7 = f32[32,8]{1,0} fusion(%x), kind=kOutput, '
               'frontend_attributes={eaas_stage="router"}',
    "%dispatch": '%fusion.8 = bf16[4,264,4096]{2,1,0} fusion(%x), '
                 'frontend_attributes={eaas_stage="dispatch"}',
    "%combine": '%fusion.9 = bf16[32,4096]{1,0} fusion(%y), '
                'frontend_attributes={eaas_stage="combine"}',
    "%attention": '%fusion.10 = bf16[32,1,4096]{2,1,0} fusion(%q), '
                  'frontend_attributes={eaas_stage="attention"}',
    "%bank": '%dynamic-slice_bitcast_fusion.24 = bf16[4,2,4096,14336]'
             '{3,2,1,0} fusion(%params, s32[] %i), kind=kLoop',
    "%cut": ('%fusion.11 = bf16[32,4096]{1,0} fusion(' + 'x' * TEXT_CHARS
             + '), frontend_attributes={eaas_stage="combine"}')[:TEXT_CHARS],
}
SECONDS = {"%gemm": 2.0, "%slots": 0.5, "%router": 0.1, "%dispatch": 0.2,
           "%combine": 0.3, "%attention": 0.4, "%bank": 0.7, "%cut": 0.9}
STEPS = 10


def _ctx(ops=OPS, steps=STEPS):
    rec = Record(t0=0.0, t1=10.0)
    rec.steps = [Step(i, i + 0.9, "decode", 0.8) for i in range(steps)]
    rec.steps.append(Step(9.95, 9.96))                 # idle: no kind
    reduced = {"op_seconds": {n: SECONDS[n] for n in ops},
               "op_text": dict(ops)}
    return run.Context(rec, None, {}, reduced, None)


def test_stage_of_each_op():
    got = {n: stages.stage_of(t) for n, t in OPS.items()}
    assert got == {"%gemm": "gemm", "%slots": "server", "%router": "router",
                   "%dispatch": "dispatch", "%combine": "combine",
                   "%attention": "attention", "%bank": None, "%cut": None}
    by = stages.by_stage(_ctx().trace)
    assert by[None] == pytest.approx(0.7 + 0.9)


def test_the_tagged_kernel_keeps_its_roofline_signature():
    # the roofline's kernel is the op whose own kernel_metadata carries the
    # gemm tag: not the server's slot copies, and not an XLA op that lies
    # in the gemm stage's region (the row pad around the kernel)
    pad = ('%fusion.12 = bf16[512,4096]{1,0} fusion(%x), '
           'frontend_attributes={eaas_stage="gemm"}')
    reduced = {"op_seconds": dict(SECONDS, **{"%pad": 0.6}),
               "op_text": dict(OPS, **{"%pad": pad})}
    assert stages.stage_of(pad) == "gemm"
    assert stages.kernel_seconds(reduced, "gemm") == SECONDS["%gemm"]
    assert stages.kernel_seconds(reduced, "server") is None


def test_gemm_roofline_by_hand():
    a = mixtral.Arch(
        name="t", d_model=1024, num_layers=2, first_k_dense=0, num_heads=8,
        num_kv_heads=2, head_dim=128, d_ff=2048, d_expert=2048,
        num_experts=4, top_k=2, num_shared_experts=0, vocab=512,
        rope_theta=1e4, rms_norm_eps=1e-6, score_fn="softmax",
        normalize_topk=True)
    ctx = _ctx()
    ctx.arch, ctx.family = a, mixtral
    ctx.peaks = {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12}
    # 10 decode steps of 32 rows over 3 of the 4 experts (summed over the
    # 2 layers); the idle step and a step past the close carry no load
    for s in ctx.rec.steps[:STEPS]:
        s.expert_load = np.array([20, 0, 24, 20])
    ctx.rec.steps.append(Step(10.0, 10.5, "decode", 0.5,
                              expert_load=np.array([64, 0, 0, 0])))
    d, f = 1024, 2048
    ops = 64 * 3 * 2 * d * f
    nbytes = 3 * 2 * 3 * d * f * 2 + 64 * 3 * (d + f) * 2
    least = STEPS * max(ops / 1e14, nbytes / 1e12)
    assert run.read_metric("gemm_roofline", ctx) == pytest.approx(
        100 * least / SECONDS["%gemm"])
    ctx.trace = {"op_seconds": {"%slots": 0.5},
                 "op_text": {"%slots": OPS["%slots"]}}
    assert run.read_metric("gemm_roofline", ctx) is None


@pytest.mark.parametrize("name, want", [
    ("expert_gemm_ms", 1e3 * 2.0 / STEPS),
    ("expert_tier_ms", 1e3 * (2.0 + 0.5 + 0.1 + 0.2 + 0.3) / STEPS),
    ("attention_ms", 1e3 * 0.4 / STEPS),
])
def test_stage_reader_by_hand(name, want):
    assert run.read_metric(name, _ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["expert_gemm_ms", "expert_tier_ms",
                                  "attention_ms"])
def test_stage_reader_finds_nothing_to_read(name):
    untagged = {n: OPS[n] for n in ("%bank", "%cut")}
    assert run.read_metric(name, _ctx(ops=untagged)) is None
    assert run.read_metric(name, _ctx(steps=0)) is None
    ctx = _ctx()
    ctx.trace = None
    assert run.read_metric(name, ctx) is None


def _plant(monkeypatch, steps):
    """Engine steps on a planted clock: each ``(kind, start, [(wait start,
    wait end), ...], end)``."""
    times = []
    for _, a, waits, b in steps:
        times += [a] + [t for w in waits for t in w] + [b]
    ticks = iter(times)
    monkeypatch.setattr(tracing, "perf_counter", lambda: next(ticks))
    for i, (kind, _, waits, _) in enumerate(steps):
        with tracing.step("engine.step", i) as step:
            for _ in waits:
                with tracing.span("executor.wait"):
                    pass
            step.set(kind=kind)


def test_step_host_ms_on_planted_spans(monkeypatch):
    t0 = 1e7            # far from the process clock: only planted spans
    _plant(monkeypatch, [
        ("decode", t0 - 10, [], t0 - 5),                  # before the window
        ("decode", t0, [(t0 + 0.002, t0 + 0.006)], t0 + 0.010),      # 6 ms
        ("prefill", t0 + 50, [(t0 + 50.001, t0 + 50.003),
                              (t0 + 50.010, t0 + 50.016)], t0 + 50.020),
        ("idle", t0 + 60, [], t0 + 60.001),                # not a step
        ("decode", t0 + 99.99, [(t0 + 100.0, t0 + 100.49)], t0 + 100.5),
        ("decode", t0 + 100.5, [], t0 + 100.6),           # after the window
    ])
    ctx = run.Context(Record(t0=t0, t1=t0 + 100), None, {}, None, None)
    # 6, 12 and 20 ms: the last step starts in the window and ends past it
    assert run.read_metric("step_host_ms", ctx) == pytest.approx(
        (6 + 12 + 20) / 3)
    empty = run.Context(Record(t0=t0 + 200, t1=t0 + 300), None, {}, None,
                        None)
    assert run.read_metric("step_host_ms", empty) is None
