"""Per-layer readers on a record worked by hand, and the judgement of
served logits against the reference's."""

import numpy as np
import pytest

from bench import run
from bench.drive import Record, Step


def _ctx():
    rec = Record(t0=0.0, t1=10.0)
    # decode steps of 1 s with the engine's bracket 0.9 s, and one prefill
    # chunk from 4.0 to 5.0
    rec.steps = [Step(t, t + 1.0, "decode", 0.9) for t in (0, 1, 2, 3)]
    rec.steps += [Step(4.0, 5.0, "prefill", 0.8)]
    rec.steps += [Step(t, t + 1.0, "decode", 0.9) for t in (5, 6, 7, 8)]
    # request 1 decodes through the prefill; request 2 starts after it
    rec.token_times = {1: [1.0, 2.0, 3.0, 4.0, 6.0, 7.0],
                       2: [6.0, 7.0, 8.0, 9.0, 10.0]}
    rec.compiles_before = {"compiles": 3, "cache_hits": 2}
    rec.compiles_after = {"compiles": 3, "cache_hits": 3}
    return run.Context(rec, None, {}, None, None)


@pytest.mark.parametrize("name, want", [
    # 5 gaps of request 1, one of them (4 -> 6) across the prefill; 3 of
    # request 2 inside the window (9 -> 10 ends at its close)
    ("prefill_gap_share", 100.0 / 8),
    ("decode_step_ms", 900.0),
    ("prefill_chunk_ms", 800.0),
    ("host_ms_per_step", 1e3 * (8 * 0.1 + 0.2) / 9),
    ("compiles_in_window", 1.0),
])
def test_reader_by_hand(name, want):
    assert run.read_metric(name, _ctx()) == pytest.approx(want)


def test_reader_finds_nothing_to_read():
    ctx = _ctx()
    ctx.rec.steps = []
    ctx.rec.token_times = {}
    for name in ("prefill_gap_share", "decode_step_ms", "host_ms_per_step",
                 "idle_share", "gemm_roofline"):
        assert run.read_metric(name, ctx) is None


def test_judge_counts_tokens_off_the_argmax():
    ref = [np.array([[0.0, 2.0, 1.0], [3.0, 0.5, 0.0]])]
    got = [np.array([[0.1, 2.1, 1.0], [0.0, 3.0, 0.5]])]
    checked, readings, correct = run.judge(ref, got, [np.array([1, 0])],
                                           {"logit_err_p90": 10.0})
    # the second token is not the argmax of the logits it came from
    assert readings["served_not_argmax"] == 1 and readings["positions"] == 2
    assert checked["served_not_argmax"] == {"value": 1, "limit": 0}
    assert correct is False
    checked, _, correct = run.judge(ref, got, [np.array([1, 1])],
                                    {"logit_err_p90": 10.0})
    assert correct is True
    _, _, correct = run.judge(ref, got, [np.array([1, 1])],
                              {"logit_err_p90": 0.01})
    assert correct is False
