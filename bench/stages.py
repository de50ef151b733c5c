"""Device time by the stage tag the program puts on its operations.

The serving path tags its device work (``src/repro/tracing.py``): an XLA
operation carries ``eaas_stage="<stage>"`` among its frontend attributes,
and the grouped GEMM kernel carries ``"eaas_stage":"<stage>"`` in its
``kernel_metadata``.  A kernel's own tag names it more closely than the
tag of the region it was traced in, which its operation also carries, so
the kernel's wins.  The profiler's text of each operation is kept cut to
``bench.trace.TEXT_CHARS``: an operation whose tag lies past the cut reads
as untagged, as do the operations XLA makes outside any tagged region.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Optional

_KERNEL_TAG = re.compile(r'\\?"eaas_stage\\?":\s*\\?"(\w+)')
_OP_TAG = re.compile(r'\beaas_stage="(\w+)"')


def stage_of(text: str) -> Optional[str]:
    """The stage an operation's text names, or None."""
    m = _KERNEL_TAG.search(text) or _OP_TAG.search(text)
    return m.group(1) if m else None


def by_stage(reduced: Dict) -> Dict[Optional[str], float]:
    """Own device seconds inside the window, summed by stage (None for the
    untagged operations)."""
    text = reduced["op_text"]
    out: Dict[Optional[str], float] = defaultdict(float)
    for name, seconds in reduced["op_seconds"].items():
        out[stage_of(text.get(name, name))] += seconds
    return dict(out)


def kernel_seconds(reduced: Dict, stage: str) -> Optional[float]:
    """Own device seconds inside the window of the kernels that carry
    ``stage`` in their own ``kernel_metadata``, or None where none ran.
    An XLA operation that only lies in the stage's region (a row pad or
    unpad around the kernel) is not one of them."""
    text = reduced["op_text"]
    hits = [seconds for name, seconds in reduced["op_seconds"].items()
            if (m := _KERNEL_TAG.search(text.get(name, name)))
            and m.group(1) == stage]
    return sum(hits) if hits else None


def ms_per_step(ctx, stages) -> Optional[float]:
    """Own device time of the operations tagged with any of ``stages``, in
    ms per engine step of the window (the steps that ran a prefill chunk
    or a decode), or None where no such operation or step is there."""
    if ctx.trace is None:
        return None
    seconds = by_stage(ctx.trace)
    if not any(s in seconds for s in stages):
        return None
    steps = sum(1 for s in ctx.rec.steps if s.kind is not None)
    if not steps:
        return None
    return 1e3 * sum(seconds.get(s, 0.0) for s in stages) / steps
