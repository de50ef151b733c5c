"""serving.engine: host time per engine step in the window, in ms, from
the program's own spans (``repro.tracing``): over the ``engine.step``
spans that start in the window and ran a prefill chunk or a decode, the
mean of the span's length less the ``executor.wait`` spans inside it,
which wait for the device.  Scheduling, the placement arrays, dispatch,
sampling and bookkeeping stay in; the harness's own work is outside the
span."""


def read(ctx):
    try:
        from repro import tracing
    except ImportError:          # a program that records no spans
        return None
    rec = ctx.rec
    spans = tracing.spans(rec.t0)
    steps = [(a, b) for name, a, b, args in spans
             if name == "engine.step" and a < rec.t1
             and args.get("kind") in ("prefill", "decode")]
    if not steps:
        return None
    waits = [(a, b) for name, a, b, _ in spans if name == "executor.wait"]
    host = [(b - a) - sum(e - s for s, e in waits if a <= s and e <= b)
            for a, b in steps]
    return 1e3 * sum(host) / len(host)
