"""serving.scheduler: 90th percentile of the wait from a request's due time
to the first step after which it holds a slot, over every request due in
the window (one still queued when the drain ends counts to that moment).
Host clock, read after each ``Cluster.step()``."""

import numpy as np


def read(ctx):
    rec = ctx.rec
    due = [r for r in rec.due if not rec.warm[r] and rec.due[r] < rec.t1]
    if not due:
        return None
    waits = [rec.slot_time.get(r, rec.drain_end) - rec.due[r] for r in due]
    return float(np.percentile(waits, 90))
