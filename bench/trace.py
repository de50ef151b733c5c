"""From a profiler trace to the numbers the benchmark reports.

Two stages.  :func:`extract` reads the ``.xplane.pb`` that
``jax.profiler`` writes into plain lists: the device's operations and the
harness's host spans (``TraceAnnotation`` names that start with
``bench.``, ``sched.`` or ``executor.``).  :func:`reduce` turns those lists
into busy time, idle share, the operations that took most time and the
idle gaps by what the host was doing.  Tests run :func:`reduce` on a small
recorded trace.

On a TPU, device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  On the CPU backend (tests only) they are the
host-thread events that carry an ``hlo_op`` stat.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

HOST_PREFIXES = ("bench.", "sched.", "executor.")
WINDOW_SPAN = "bench.window"
TEXT_CHARS = 600


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(path: str) -> Dict:
    """``{"device": {plane: [[op, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...], "ops": {op: its HLO text}}``.
    A device op is named by its HLO instruction name (``%fusion.12``); its
    text (shapes, operands, custom-call target) is kept once in ``ops``,
    cut to ``TEXT_CHARS``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, List] = defaultdict(list)
    host: List = []
    ops: Dict[str, str] = {}

    def add(plane, text, ev):
        name = text.split(" = ", 1)[0]
        ops.setdefault(name, text[:TEXT_CHARS])
        device[plane].append([name, ev.start_ns, ev.duration_ns])

    for plane in pd.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        add(plane.name, ev.name, ev)
                continue
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIXES):
                    host.append([ev.name, ev.start_ns, ev.duration_ns])
                elif plane.name.startswith("/host:") and any(
                        k == "hlo_op" for k, _ in ev.stats):
                    add("/host:cpu-ops", ev.name, ev)
    return {"device": dict(device), "host": host, "ops": ops}


def self_times(ops: List) -> List:
    """``[[op, start, end, self_ns], ...]``: an op's own time, less the ops
    nested inside it on the same line (a ``while`` holds its body's ops)."""
    out = []
    stack: List = []
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        e = s + d
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [name, s, e, d]
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append(rec)
        out.append(rec)
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def window_of(ev: Dict) -> Tuple[float, float]:
    """(start_ns, end_ns) of the harness's window span."""
    spans = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0]


def reduce(ev: Dict, top: int = 10) -> Dict:
    """Busy and idle time inside the window span, averaged over devices,
    with the operations that took most time (their own time, nesting
    taken out) and the idle gaps by the innermost host span around them."""
    lo, hi = window_of(ev)
    planes = sorted(ev["device"])
    if not planes:
        raise ValueError("the trace holds no device operation")
    busy, op_s, gap_s = 0.0, defaultdict(float), defaultdict(float)
    host = sorted(((s, s + d, n) for n, s, d in ev["host"]
                   if n != WINDOW_SPAN), key=lambda h: h[1] - h[0])
    for plane in planes:
        inside = []
        for name, s, e, own in self_times(ev["device"][plane]):
            a, b = max(s, lo), min(e, hi)
            if b > a:
                inside.append((a, b))
                # an op cut by the window keeps its share of its own time
                op_s[name] += own * (b - a) / (e - s) / len(planes) * 1e-9
        merged = _union(inside)
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            what = next((n for s, e, n in host if s <= mid < e), "host.none")
            gap_s[what] += (b - a) / len(planes) * 1e-9
    busy_s = busy / len(planes) * 1e-9
    window_s = (hi - lo) * 1e-9
    text = ev.get("ops", {})
    ranked = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "device_ops": [[text.get(k, k)[:160], v] for k, v in ranked],
            "idle_gaps": [[k, v] for k, v in sorted(
                gap_s.items(), key=lambda kv: -kv[1])[:top]],
            "op_seconds": dict(op_s), "op_text": text}


def op_seconds(reduced: Dict, pattern: str) -> Optional[float]:
    """Own device seconds of the operations whose HLO text matches
    ``pattern``, inside the window, or None where none ran."""
    rx = re.compile(pattern)
    text = reduced["op_text"]
    hits = [s for n, s in reduced["op_seconds"].items()
            if rx.search(text.get(n, n))]
    return sum(hits) if hits else None
