"""The reduction from a profiler trace to busy time, idle share, top
operations and idle gaps by host activity."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import stages, trace

# the Pallas grouped GEMM of the recorded traces by its operands: a
# tpu_custom_call on the two scalar-prefetch tile tables, the sorted rows
# and the (groups, K, N) weight bank
SIGNATURE = (r'custom-call\(s32\[\d+\]\{[^}]*\} %[\w.-]+, '
             r's32\[\d+\]\{[^}]*\} %[\w.-]+, '
             r'bf16\[\d+,\d+\]\{[^}]*\} %[\w.-]+, '
             r'bf16\[\d+,\d+,\d+\]\{[^}]*\} %[\w.-]+\), '
             r'custom_call_target="tpu_custom_call"')


def _ev(device, host):
    return {"device": {"/device:TPU:0": device}, "host": host}


def test_reduce_by_hand():
    # window 0..100 ns; ops cover 10..30 and 25..40 (union 10..40) and
    # 70..80; the 40..70 gap sits inside the sampling span
    ev = _ev([["fusion.1", 10, 20], ["gemm", 25, 15], ["gemm", 70, 10]],
             [["bench.window", 0, 100], ["bench.step", 0, 100],
              ["executor.decode", 5, 30], ["bench.observe", 40, 35]])
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["device_ops"][0] == ["gemm", pytest.approx(25e-9)]
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # 0..10 inside executor.decode, 40..70 inside bench.observe,
    # 80..100 inside bench.step only
    assert gaps == {"executor.decode": pytest.approx(10e-9),
                    "bench.observe": pytest.approx(30e-9),
                    "bench.step": pytest.approx(20e-9)}
    assert trace.op_seconds(r, r"^gemm$") == pytest.approx(25e-9)
    assert trace.op_seconds(r, "nothing") is None


def test_ops_outside_the_window_are_clipped():
    ev = _ev([["a", 0, 50], ["b", 90, 50]],
             [["bench.window", 20, 100]])
    r = trace.reduce(ev)
    assert r["busy_s"] == pytest.approx(60e-9)
    assert dict((k, v) for k, v in r["device_ops"]) == {
        "a": pytest.approx(30e-9), "b": pytest.approx(30e-9)}


def test_reduce_averages_over_devices():
    ev = {"device": {"/device:TPU:0": [["x", 0, 100]],
                     "/device:TPU:1": [["x", 0, 50]]},
          "host": [["bench.window", 0, 100]]}
    assert trace.reduce(ev)["busy_s"] == pytest.approx(75e-9)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(_ev([["x", 0, 1]], []))


def test_extract_from_a_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            time.sleep(0.005)
    jax.profiler.stop_trace()
    ev = trace.extract(trace.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in ev["host"]]
    assert names.count("bench.window") == 1 and names.count("bench.step") == 3
    r = trace.reduce(ev)
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    assert r["device_ops"]


def _recorded(cell="r1.reason"):
    import gzip
    import json
    from pathlib import Path

    path = Path(__file__).with_name("data") / f"{cell}.trace.json.gz"
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_chip_trace():
    """0.4 s of an r1.reason window on one TPU v5e (the events as
    :func:`trace.extract` keeps them), reduced."""
    r = trace.reduce(_recorded())
    assert r["window_s"] == pytest.approx(0.4)
    assert r["busy_s"] == pytest.approx(0.384330765)
    assert r["idle_share"] == pytest.approx(0.0391730875)
    # the scan over layers holds its body's ops: its own time is small,
    # so the grouped GEMM's three calls lead
    assert all("custom-call" in n for n, _ in r["device_ops"][:3])
    assert not any(n.startswith("%while") for n, _ in r["device_ops"][:5])
    assert trace.op_seconds(r, SIGNATURE) == pytest.approx(0.214620911)
    # the trace predates the kernel's own tag (kernel_metadata={}): the
    # roofline finds no kernel in it, and reads nothing
    assert stages.kernel_seconds(r, "gemm") is None
    assert dict(r["idle_gaps"])["bench.step"] == pytest.approx(0.013801973)


def test_recorded_kernel_by_its_own_tag():
    """0.4 s of an mx.reason window on one TPU v5e, from a program that
    tags its kernels: the roofline's selection by the kernel's own tag
    picks exactly the ops the operand signature picks, its three grouped
    GEMM calls."""
    import re

    r = trace.reduce(_recorded("mx.reason"))
    assert r["window_s"] == pytest.approx(0.4)
    assert r["busy_s"] == pytest.approx(0.370164085)
    text = r["op_text"]
    tagged = {n for n in r["op_seconds"]
              if (m := stages._KERNEL_TAG.search(text[n]))
              and m.group(1) == "gemm"}
    signed = {n for n in r["op_seconds"] if re.search(SIGNATURE, text[n])}
    assert tagged == signed == {"%closed_call.42", "%closed_call.43",
                                "%closed_call.44"}
    assert stages.kernel_seconds(r, "gemm") == pytest.approx(0.056509534)
    assert trace.op_seconds(r, SIGNATURE) == pytest.approx(0.056509534)
