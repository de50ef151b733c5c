"""models.mla: the least time the latent attention of the window's steps
needs on this chip, over the own device time of the operations tagged
``mla``, in %.

The work is counted by the family module's ``mla_work``: for each step,
a decode query for each token it made, at that request's actual context
(token ``i`` > 0 of a request with a prompt of ``p`` tokens came from the
decode step that ended when it became visible, attending ``p + i``
positions), and the prompt chunk the scheduler planned, if any; the
attention weights once a step and the latent cache read up to each
context.  Slots with no request and cache slots past a context are not
counted, so work an implementation could skip is not charged.  Least
time per step is the larger of operations over peak bf16 rate and bytes
over HBM bandwidth.  A family without ``mla_work`` reads nothing."""

from collections import defaultdict

from bench.stages import by_stage


def read(ctx):
    work = getattr(ctx.family, "mla_work", None)
    if ctx.trace is None or work is None:
        return None
    seconds = by_stage(ctx.trace).get("mla")
    if not seconds:
        return None
    rec, a, pk = ctx.rec, ctx.arch, ctx.peaks
    keys = defaultdict(list)          # a step's end -> its decode contexts
    for rid, times in rec.token_times.items():
        p = rec.prompt_len[rid]
        for i, t in enumerate(times):
            if i > 0 and rec.t0 <= t < rec.t1:
                keys[t].append(p + i)
    least = 0.0
    for s in rec.steps:
        if s.kind is None or s.end >= rec.t1:
            continue
        chunks = ([(s.plan.start, s.plan.length)]
                  if s.kind == "prefill" and s.plan is not None else [])
        ops, nbytes = work(a, keys=keys.get(s.end, ()), chunks=chunks)
        least += max(ops / pk["bf16_flops_per_s"],
                     nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds if least else None
