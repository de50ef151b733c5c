"""serving.scheduler: share of the window's inter-token gaps that span a
prefill-chunk step, in %.  Under prefill-priority a prompt's chunks run
while every decoding request waits, so these gaps form the slow mode of
the gap distribution, and the time they take is lost to ``tok_per_s``.
Host clock, from the harness's step spans."""

import bisect


def read(ctx):
    rec = ctx.rec
    starts = sorted(s.start for s in rec.steps if s.kind == "prefill")
    total = spanning = 0
    for ts in rec.token_times.values():
        for a, b in zip(ts, ts[1:]):
            if rec.t0 <= a and b < rec.t1:
                total += 1
                i = bisect.bisect_left(starts, a)
                spanning += i < len(starts) and starts[i] < b
    return 100.0 * spanning / total if total else None
