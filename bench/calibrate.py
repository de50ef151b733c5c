#!/usr/bin/env python3
"""Read the numbers ``correct`` compares, for the served path and for the
lower-precision controls put in its place, over many seeds in one
process (the set-up of each seed is paid once, not once per control).

    python3 bench/calibrate.py --workload mx.reason --seconds 20 \\
        --seeds 1 2 3 --controls int8 fp8 --out cal.jsonl

Each seed is a whole run of the cell, as ``bench/run.py`` makes it; one
JSON line per seed gives every reading of the served path and of each
control, and whether each came out correct under the configuration's
limits.  The limits are set from these readings (see PERF.md).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)          # bench/trace.py must not shadow stdlib trace
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=["int8", "fp8"])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    bm = run.load_benchmark()
    cell, cfg, mix = run.cell_parts(bm, args.workload)
    device = run.require_chips(int(cell["chips"]))
    from bench.peaks import peaks
    chip_peaks = peaks(device["kind"])
    run.enable_compile_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = run.run_cell(cell, cfg, mix, seed=seed, seconds=args.seconds,
                           trace=False, metric_names=run.metrics_of(
                               bm, cell["name"], "end_to_end"),
                           device=device, peaks=chip_peaks, t_start=t0,
                           controls=args.controls)
        line = json.dumps({"seed": seed, "correct": out["correct"],
                           "readings": out["readings"],
                           "controls": {q: {"correct": c["correct"],
                                            "readings": c["readings"]}
                                        for q, c in out["controls"].items()},
                           "metrics": out["metrics"],
                           "memory_peak_bytes":
                               out["device"]["memory_peak_bytes"]})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
