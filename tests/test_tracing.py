"""repro.tracing on the CPU: the span ring, the counters, the compile
listener, the MoE layer's dropped-row and grouped GEMM tile counts, and the
stage tags of the compiled decode program (every stage is there, and the
tags change nothing the program computes)."""

import contextlib
import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import system
from bench.arch import arch_of
from repro import tracing
from repro.models.transformer import ParallelCtx
from repro.serving import Request, SamplingParams, ServingEngine

ROOT = Path(__file__).resolve().parent.parent
STAGES = ("attention", "router", "dispatch", "server", "gemm", "combine",
          "head")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%\S+ = ", re.M)


def _tiny_config(pool_tokens=None, gemm_impl=None) -> dict:
    """The benchmark's configuration at the tiny widths of
    ``tests/bench/bench_tiny.py``, whose buffers are sized dropless."""
    spec = importlib.util.spec_from_file_location(
        "tracing_bench_tiny", ROOT / "tests" / "bench" / "bench_tiny.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cfg = mod.tiny_config()
    if pool_tokens is not None:
        cfg["serving"]["pool_tokens_per_client"] = pool_tokens
    if gemm_impl is not None:
        cfg["serving"]["gemm_impl"] = gemm_impl
    return cfg


def _engine(pool_tokens=None, gemm_impl=None) -> ServingEngine:
    cfg = _tiny_config(pool_tokens, gemm_impl)
    a = arch_of(cfg)
    return ServingEngine(system.model_config(cfg, a),
                         system.engine_config(cfg["serving"]), seed=3)


def _serve(eng, n: int = 4, prompt: int = 64, new: int = 6):
    rng = np.random.default_rng(5)
    reqs = [Request(i, rng.integers(0, 512, prompt).astype(np.int32),
                    SamplingParams(temperature=0.0, max_new_tokens=new))
            for i in range(n)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return [list(r.output_tokens) for r in reqs]


def _decode_text(eng) -> str:
    """The optimized HLO of the engine's decode step at its slot batch."""
    rt = eng.pool.runtime(eng.ecfg.gemm_impl)
    ctx = ParallelCtx(moe_runtime=rt, gemm_impl=eng.ecfg.gemm_impl,
                      remat=False)
    ex = eng.executor
    step = jax.jit(lambda p, t, c: eng.model.decode_step(p, t, c, ctx))
    tokens = jnp.zeros((ex.max_batch, 1), jnp.int32)
    return step.lower(ex.params, tokens, ex.cache).compile().as_text()


def test_ring_keeps_its_size_and_reads_a_window(monkeypatch):
    ticks = iter(range(10 ** 7))
    monkeypatch.setattr(tracing, "perf_counter", lambda: float(next(ticks)))
    n = tracing.RING_SIZE + 5
    for i in range(n):
        with tracing.span("test.ring", i=i):
            pass
    ring = tracing.spans()
    assert len(ring) == tracing.RING_SIZE
    # the oldest went; each span started at 2 i and ended at 2 i + 1
    assert [s[3]["i"] for s in ring] == list(range(5, n))
    assert ring[0][:3] == ("test.ring", 10.0, 11.0)
    window = tracing.spans(40.0, 50.0)
    assert [s[3]["i"] for s in window] == list(range(20, 25))


def test_nested_spans_nest():
    with tracing.span("test.outer"):
        with tracing.span("test.inner", k=1):
            pass
    inner, outer = tracing.spans()[-2:]
    assert (inner[0], outer[0]) == ("test.inner", "test.outer")
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
    assert inner[3] == {"k": 1}
    with tracing.step("test.step", 7) as step:
        step.set(kind="decode")
    assert tracing.spans()[-1][3] == {"step_num": 7, "kind": "decode"}


def test_counters_add_up():
    before = tracing.counters().get("test.count", 0)
    tracing.count("test.count")
    tracing.count("test.count", 4)
    got = tracing.counters()
    assert got["test.count"] == before + 5
    got["test.count"] = -1                    # a copy, not the counters
    assert tracing.counters()["test.count"] == before + 5


def test_compile_counter_counts_steps_apart():
    tracing.watch_compiles()
    tracing.watch_compiles()                  # one listener per process
    x = np.arange(8, dtype=np.float32)

    def programs(c):
        return c.get("jax.compiles", 0) + c.get("jax.cache_hits", 0)

    c0 = tracing.counters()
    jax.jit(lambda v: v * 3.0 + 0.25)(x)
    c1 = tracing.counters()
    assert programs(c1) - programs(c0) == 1
    assert c1.get("jax.compile_s", 0) >= c0.get("jax.compile_s", 0)
    assert (c1.get("engine.step_compiles", 0)
            == c0.get("engine.step_compiles", 0))
    with tracing.step("engine.step", 1):
        jax.jit(lambda v: v * 5.0 - 0.75)(x)
    c2 = tracing.counters()
    assert programs(c2) - programs(c1) == 1
    assert (c2.get("engine.step_compiles", 0)
            == c1.get("engine.step_compiles", 0) + 1)


@pytest.mark.parametrize("pool_tokens, dropless", [(None, True), (8, False)],
                         ids=["dropless", "pool_too_small"])
def test_dropped_rows_are_counted(pool_tokens, dropless):
    eng = _engine(pool_tokens)
    before = tracing.counters().get("moe.dropped_rows", 0)
    _serve(eng)
    dropped = tracing.counters().get("moe.dropped_rows", 0) - before
    assert (dropped == 0) if dropless else (dropped > 0)


@pytest.mark.parametrize("gemm_impl, counted",
                         [("pallas_interpret", True), ("xla_ragged", False)])
def test_gemm_row_tiles_are_counted(gemm_impl, counted):
    """The Pallas kernel's row tiles, live and laid out, reach the
    counters; the XLA lowering has none."""
    names = ("gemm.tiles_live", "gemm.tiles")
    eng = _engine(gemm_impl=gemm_impl)
    before = [tracing.counters().get(k, 0) for k in names]
    _serve(eng, n=2, prompt=32, new=3)
    live, total = (tracing.counters().get(k, 0) - b
                   for k, b in zip(names, before))
    assert (0 < live <= total) if counted else (live == total == 0)


def test_every_stage_is_in_the_decode_program():
    text = _decode_text(_engine())
    for stage in STAGES:
        assert f'{tracing.STAGE_KEY}="{stage}"' in text, stage


def test_stage_tags_change_nothing(monkeypatch):
    tagged = _decode_text(_engine())
    tokens = _serve(_engine())
    monkeypatch.setattr(tracing, "stage",
                        lambda name: contextlib.nullcontext())
    plain = _decode_text(_engine())
    assert tracing.STAGE_KEY not in plain
    assert (len(INSTRUCTION.findall(plain))
            == len(INSTRUCTION.findall(tagged)))
    assert _serve(_engine()) == tokens
