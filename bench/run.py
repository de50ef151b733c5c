#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload mx.reason --seed 7 --seconds 10 --trace 0

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<name>.json``, whose ``model_type`` names its family module
``bench/models/<model_type>.py``) under a traffic mix
(``bench/traffic/<name>.json``).  One run builds the configuration's
serving system with weights drawn on the device from ``--seed``, serves the
mix's set-up traffic (that warms every program the window uses), offers
the mix open loop for ``--seconds``, and then checks a seeded sample of
the requests it served against the float32 reference.  With ``--trace 1``
the window runs under the profiler and the cell's per-layer metrics are
reported (each read by ``bench/metrics/<name>.py``) in place of the
end-to-end ones.

The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``checked``: each number compared, with its limit.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)          # bench/trace.py must not shadow stdlib trace
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_parts(bm: dict, name: str):
    """(cell, configuration file, traffic mix) of a named cell."""
    from bench.models import load_config

    cells = {c["name"]: c for c in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    mix_path = BENCH / "traffic" / f"{cell['traffic']}.json"
    return cell, load_config(cell["config"]), json.loads(mix_path.read_text())


def metrics_of(bm: dict, cell: str, kind: str):
    """The cell's end-to-end or per-layer metric entries."""
    return [m for m in bm[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(name: str, ctx) -> float | None:
    """Run ``bench/metrics/<name>.py``'s ``read(ctx)``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def require_chips(n: int) -> dict:
    """The device report; refuses anything but ``n`` or more TPU chips."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {d.platform!r} "
                         f"({d.device_kind})")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``<checkout>/.jax_cache``: a fixed path (it
    is part of the key) inside the checkout, so that two checkouts measured
    side by side share no compiled program."""
    import jax

    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Context:
    """What a per-layer reader sees; ``family`` is the configuration's
    family module, which counts its operations."""

    def __init__(self, rec, arch, peaks, reduced, family):
        self.rec = rec
        self.arch = arch
        self.family = family
        self.peaks = peaks
        self.trace = reduced
        self.window_s = rec.t1 - rec.t0


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(rec, t_setup: float) -> dict:
    """Every end-to-end number a run can report, from the client's side."""
    t0, t1 = rec.t0, rec.t1
    visible = [t for ts in rec.token_times.values() for t in ts
               if t0 <= t < t1]
    due = [r for r in rec.due if not rec.warm[r] and rec.due[r] < t1]
    ttft = [(rec.token_times[r][0] if rec.token_times[r] else rec.drain_end)
            - rec.due[r] for r in due]
    out = {"tok_per_s": len(visible) / (t1 - t0), "setup_s": t_setup}
    if ttft:
        out["ttft_p90_s"] = percentile(ttft, 90)
    return out


def check_sample(requests, mix: dict, seed: int):
    """The requests whose served tokens are checked: the one with the most
    served tokens, and the rest drawn from the seed among all that were
    served a token."""
    served = sorted(r for r in requests if requests[r].output_tokens)
    k = int(mix["check"]["requests"])
    if not served:
        return []
    longest = max(served, key=lambda r: len(requests[r].output_tokens))
    rest = [r for r in served if r != longest]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(rest, size=min(k - 1, len(rest)), replace=False)
    return [longest] + sorted(int(r) for r in pick)


def run_cell(cell: dict, cfg: dict, mix: dict, *, seed: int, seconds: float,
             trace: bool, metric_names, device: dict, peaks: dict,
             t_start: float = T_START, keep_trace: str = "",
             controls=()) -> dict:
    """One run of a cell; returns the result object (without printing).
    ``keep_trace`` saves the extracted trace events there; ``controls``
    (``"int8"``, ``"fp8"``) also judges each lower-precision control at the
    same positions, under ``"controls"``, with the served path's readings
    under ``"readings"`` (calibration only)."""
    import jax

    from bench import system, workload
    from bench import trace as trace_mod
    from bench.compiles import CompileCounter
    from bench.drive import Driver
    from bench.models import family

    counter = CompileCounter()
    fam = family(cfg)
    a = fam.arch(cfg)
    s = cfg["serving"]
    planned = workload.plan(mix, seed=seed, seconds=seconds, vocab=a.vocab,
                            max_seq=s["max_seq"], chunk=s["prefill_chunk"],
                            slots=s["max_batch"])
    warm = [p for p in planned if p.warm]
    offered = [p for p in planned if not p.warm]
    cluster, eng, clock, timings = system.build(cfg, a, seed)
    annotate = jax.profiler.TraceAnnotation if trace else None
    driver = Driver(cluster, eng, clock, counter, annotate=annotate)
    t_warm = time.perf_counter()
    driver.warm(warm, decode_steps=int(mix["warm"].get("decode_steps", 2)),
                until_done=not mix["warm"].get("fill_slots", False))
    log(f"weights {timings.get('weights_s', 0):.2f} s; set-up traffic "
        f"({len(warm)} requests) {time.perf_counter() - t_warm:.2f} s; "
        f"compiles so far {counter.snapshot()}")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    t_setup = time.perf_counter() - t_start
    rec = driver.window(offered, seconds, float(mix.get("drain_s", 0)))
    if trace:
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:device["count"]])

    for st in rec.steps:
        if st.expert_load is not None:
            st.expert_load = np.asarray(st.expert_load)
    requests = driver.requests
    served = {rid: (np.asarray(r.prompt), np.asarray(r.output_tokens,
                                                     np.int64))
              for rid, r in requests.items()}
    sample = check_sample(requests, mix, seed)
    program_logits = {r: [driver.logits.get((r, i)) for i in
                          range(len(requests[r].output_tokens))]
                      for r in sample}
    n_due = sum(1 for p in offered if p.due < seconds)
    failed = 0
    if mix.get("drain_s", 0) > 0:
        failed = sum(1 for p in offered if p.due < seconds
                     and not rec.token_times[p.rid])
    queued = sum(1 for p in offered if p.rid in rec.due
                 and not rec.token_times[p.rid])
    log(f"window {seconds} s: {len(rec.steps)} steps, "
        f"{len(rec.submit_lag)} offered, {queued} without a first token; "
        f"generator late by at most "
        f"{max(rec.submit_lag, default=0.0) * 1e3:.1f} ms; compiles "
        f"{rec.compiles_before} -> {rec.compiles_after}; device memory "
        f"peak {peak} of {stats.get('bytes_limit', 0)} bytes")

    # the program's state goes before the reference runs
    del driver, cluster, eng, clock
    gc.collect()
    log(f"live device bytes after the program's state went: "
        f"{sum(x.nbytes for x in jax.live_arrays())}")

    e2e = end_to_end(rec, t_setup)
    result_metrics = {}
    breakdown = None
    dev = dict(device, memory_peak_bytes=int(peak))
    if trace:
        events = trace_mod.extract(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if keep_trace:
            Path(keep_trace).parent.mkdir(parents=True, exist_ok=True)
            with gzip.open(keep_trace, "wt") as f:
                json.dump(events, f)
        reduced = trace_mod.reduce(events)
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        ctx = Context(rec, a, peaks, reduced, fam)
        for m in metric_names:
            v = read_metric(m["name"], ctx)
            if v is not None:
                result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in metric_names:
            if m["name"] in e2e:
                result_metrics[m["name"]] = {"value": e2e[m["name"]],
                                             "unit": m["unit"]}
    del requests

    t_ref = time.perf_counter()
    picked = [served[r] + (program_logits[r],) for r in sample]
    judged = check(fam, a, cfg, seed, picked, controls=controls,
                   batch=int(mix["check"]["requests"]))
    log(f"reference over {len(sample)} requests "
        f"{time.perf_counter() - t_ref:.1f} s")
    for who, (_, readings, ok) in judged.items():
        log(f"readings of the {who} logits (correct {ok}): {readings}")
    checked, _, correct = judged["served"]
    out = {"correct": correct, "attempted": n_due + len(warm),
           "failed": failed, "metrics": result_metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if controls:
        out["controls"] = {q: {"correct": judged[q][2],
                               "checked": judged[q][0],
                               "readings": judged[q][1]} for q in controls}
        out["readings"] = judged["served"][1]
    out["checked"] = checked
    return out


def logit_stats(ref, got) -> dict:
    """Relative L2 error of each position's logits against the reference's,
    over the vocabulary: its median, 75th, 90th and 95th percentiles and
    largest."""
    err = np.concatenate([np.linalg.norm(g - r, axis=-1)
                          / np.linalg.norm(r, axis=-1)
                          for r, g in zip(ref, got)])
    return {"logit_err_median": float(np.median(err)),
            "logit_err_p75": float(np.percentile(err, 75)),
            "logit_err_p90": float(np.percentile(err, 90)),
            "logit_err_p95": float(np.percentile(err, 95)),
            "logit_err_max": float(err.max())}


def judge(ref, got, tokens, limits: dict):
    """(checked numbers with their limits, every reading, correct) of
    ``got``, the logits each of ``tokens`` was chosen from, against the
    reference's logits at the same positions."""
    readings = {"served_not_argmax": int(sum(
        np.sum(g.argmax(-1) != t) for g, t in zip(got, tokens))),
        "positions": int(sum(len(t) for t in tokens))}
    readings.update(logit_stats(ref, got))
    checked = {"served_not_argmax": {"value": readings["served_not_argmax"],
                                     "limit": 0}}
    for name, limit in limits.items():
        checked[name] = {"value": readings[name], "limit": limit}
    correct = all(c["value"] <= c["limit"] for c in checked.values())
    return checked, readings, correct


def check(fam, a, cfg: dict, seed: int, sample, controls=(),
          batch: int = 0):
    """Judge what the served path produced for ``sample`` ((prompt, served
    tokens, the logits each was chosen from) triples) against the float32
    reference of the configuration's family module ``fam``.  Returns
    ``{"served": (checked, readings, correct)}``, and the same for each
    control in ``controls`` (``int8``, ``fp8``): the reference computed in
    that precision, put in the served path's place at the same positions,
    with the tokens it puts first."""
    complete = bool(sample) and all(
        len(o) and all(x is not None for x in lg) for _, o, lg in sample)
    if not complete:
        miss = ({"sampled_requests": {"value": len(sample), "limit": 1}},
                {}, False)
        return {k: miss for k in ("served", *controls)}
    V = a.vocab
    seqs = [np.concatenate([p, o[:-1]]).astype(np.int32)
            for p, o, _ in sample]
    reads = [np.arange(len(p) - 1, len(p) - 1 + len(o)) for p, o, _ in sample]
    got = [np.stack(lg)[:, :V].astype(np.float32) for _, _, lg in sample]
    # one padded shape for every run of the configuration: its programs
    # compile once and come from the cache after
    pad = dict(pad_to=cfg["serving"]["max_seq"], batch=batch)
    ref = fam.forward(a, seed, seqs, reads, **pad)
    out = {"served": judge(ref, got, [o for _, o, _ in sample],
                           cfg["check"])}
    for q in controls:
        low = fam.forward(a, seed, seqs, reads, quant=q, **pad)
        out[q] = judge(ref, low, [x.argmax(-1) for x in low], cfg["check"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="also save the trace's extracted events (.json.gz)")
    ap.add_argument("--control", default="", choices=("", "int8", "fp8"),
                    help="put this lower-precision control in the served "
                         "path's place: the result judges its logits, and "
                         "should read correct false")
    args = ap.parse_args(argv)

    bm = load_benchmark()
    cell, cfg, mix = cell_parts(bm, args.workload)
    device = require_chips(int(cell["chips"]))
    from bench.peaks import peaks
    chip_peaks = peaks(device["kind"])
    log(f"device {device}; compile cache {enable_compile_cache()}")
    kind = "per_layer" if args.trace else "end_to_end"
    out = run_cell(cell, cfg, mix, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace),
                   metric_names=metrics_of(bm, cell["name"], kind),
                   device=device, peaks=chip_peaks,
                   keep_trace=args.keep_trace,
                   controls=[args.control] if args.control else [])
    if args.control:
        ctl = out.pop("controls")[args.control]
        del out["readings"]
        out["correct"], out["checked"] = ctl["correct"], ctl["checked"]
    for name, c in out["checked"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
