"""model step: model operations of every token the window processed, over
the window's seconds and the chip's peak bf16 rate, in %.

Counted from the configuration's shapes at each token's actual context
by its family module's ``token_flops`` and ``chunk_flops``: each decode
token of a served request at its position, each prefill chunk the
scheduler planned, causal attention over the tokens in context.  Slots
with no request, padding and replica slots are not counted."""


def read(ctx):
    rec, a, fam = ctx.rec, ctx.arch, ctx.family
    total = 0.0
    for s in rec.steps:
        if s.kind == "prefill" and s.plan is not None and s.end < rec.t1:
            total += fam.chunk_flops(a, s.plan.start, s.plan.length)
    for rid, times in rec.token_times.items():
        p = rec.prompt_len[rid]
        # token i > 0 came from a decode step at position p + i - 1
        total += sum(fam.token_flops(a, p + i - 1)
                     for i, t in enumerate(times)
                     if i > 0 and rec.t0 <= t < rec.t1)
    if total == 0:
        return None
    return 100.0 * total / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
