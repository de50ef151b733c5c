"""``correct`` on the CPU at the tiny size: a sound run of the whole
harness passes, and the lower-precision control, put in the served path's
place, comes out not correct.  (The chip's limit is set from chip readings
at the cell's own size; see PERF.md.)"""

from bench_tiny import tiny_run

# the tiny size's readings of the compared number (the 90th percentile of
# the logits' relative error: served path 0.010-0.011 on seeds 1-3, fp8
# control 0.107-0.137) put the limit between them
LIMIT = 0.03


def test_sound_run_is_correct():
    out = tiny_run("reason", seed=2 ** 31 + 21, limit=LIMIT,
                   controls=("int8", "fp8"))
    assert out["correct"] is True
    assert list(out)[-1] == "checked"
    (name, err), = [(k, v) for k, v in out["checked"].items()
                    if k != "served_not_argmax"]
    assert err["value"] <= err["limit"] == LIMIT
    assert out["checked"]["served_not_argmax"] == {"value": 0, "limit": 0}
    r = out["readings"]
    assert r["positions"] >= 100
    assert set(out["metrics"]) == {"tok_per_s", "setup_s"}
    # the control (fp8) reads at least three times the served path at the
    # same positions and comes out not correct; int8 reads above the
    # served path
    fp8, int8 = out["controls"]["fp8"], out["controls"]["int8"]
    assert fp8["readings"][name] > 3 * r[name]
    assert fp8["correct"] is False
    assert fp8["checked"][name]["value"] > LIMIT
    assert int8["readings"][name] > r[name]


def test_chat_run_reports_ttft_and_is_correct():
    out = tiny_run("chat", seed=12, seconds=3.0, limit=LIMIT, metrics=[
        {"name": n, "unit": "s"} for n in ("tok_per_s", "ttft_p90_s",
                                           "setup_s")])
    assert out["correct"] is True
    assert {"tok_per_s", "ttft_p90_s", "setup_s"} <= set(out["metrics"])
    assert out["failed"] == 0 and out["attempted"] > 4
