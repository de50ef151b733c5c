"""What the serving path records about itself: device stage tags, host
spans and counters.  Always on, and bounded.

* :func:`stage` tags the device work traced inside it.  It enters
  ``jax.named_scope`` (XProf's framework-op view groups by it) and sets
  the XLA frontend attribute ``eaas_stage``, which the compiled program
  keeps on each instruction and the profiler shows in the op's text.  It
  runs at trace time and adds nothing at run time.  Stages: ``attention``
  (GQA), ``mla`` (latent attention, in its place), ``router``,
  ``dispatch``, ``server``, ``combine``, ``head``; the grouped GEMM kernel
  carries ``gemm`` in its own ``kernel_metadata``.
* :class:`span` marks host work: a ``jax.profiler.TraceAnnotation``, so it
  lies on the device trace's clock, and a record ``(name, start, end,
  args)`` on ``time.perf_counter`` in a process-wide ring of
  :data:`RING_SIZE` spans (:func:`spans` reads it).  :class:`step` is the
  engine step's span, a ``StepTraceAnnotation``.
* :func:`count` adds to a process-wide, monotonic counter;
  :func:`counters` reads them all.  :func:`watch_compiles` registers the
  one JAX monitoring listener that counts ``jax.compiles``,
  ``jax.compile_s``, ``jax.cache_hits`` and ``engine.step_compiles`` (the
  compiles and persistent-cache loads made while an engine step is open).
"""

from __future__ import annotations

import collections
import contextlib
import threading
from time import perf_counter
from typing import Dict, List, Tuple

import jax
from jax.experimental.xla_metadata import set_xla_metadata

STAGE_KEY = "eaas_stage"
RING_SIZE = 2 ** 16

_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_counters: Dict[str, float] = collections.Counter()
_open_steps = 0
_watching = False
_watch_lock = threading.Lock()

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@contextlib.contextmanager
def stage(name: str):
    """Tag the device work traced inside with stage ``name``."""
    with jax.named_scope(name), set_xla_metadata(**{STAGE_KEY: name}):
        yield


class span:
    """``with span("executor.decode"):`` — host work, on the profiler's
    trace and in the ring.  ``set(**args)`` adds arguments while it is
    open."""

    _annotation = jax.profiler.TraceAnnotation

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self):
        self._tm = self._annotation(self.name, **self.args)
        self._tm.__enter__()
        self._t0 = perf_counter()
        return self

    def set(self, **args) -> None:
        self.args.update(args)
        self._tm.set_metadata(**args)

    def __exit__(self, *exc):
        t1 = perf_counter()
        self._tm.__exit__(*exc)
        _ring.append((self.name, self._t0, t1, self.args))
        return False


class step(span):
    """One engine step, numbered: XProf groups device work by it.  Compiles
    made while it is open count as ``engine.step_compiles``."""

    _annotation = jax.profiler.StepTraceAnnotation

    def __init__(self, name: str, step_num: int, **args):
        super().__init__(name, step_num=step_num, **args)

    def __enter__(self):
        global _open_steps
        _open_steps += 1
        return super().__enter__()

    def __exit__(self, *exc):
        global _open_steps
        _open_steps -= 1
        return super().__exit__(*exc)


def spans(t0: float = float("-inf"), t1: float = float("inf")
          ) -> List[Tuple[str, float, float, dict]]:
    """The ring's spans that started in ``[t0, t1)``, in the order they
    ended."""
    return [s for s in list(_ring) if t0 <= s[1] < t1]


def count(name: str, n: float = 1) -> None:
    _counters[name] += n


def counters() -> Dict[str, float]:
    """A copy of every counter."""
    return dict(_counters)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        count("jax.compiles")
        count("jax.compile_s", duration)
        if _open_steps:
            count("engine.step_compiles")


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        count("jax.cache_hits")
        if _open_steps:
            count("engine.step_compiles")


def watch_compiles() -> None:
    """Register the compile listener, once per process (JAX keeps a
    listener for the life of the process)."""
    global _watching
    with _watch_lock:
        if _watching:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _watching = True
