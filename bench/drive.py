"""Set-up traffic, the measured window and the drain, on the client's side.

Requests are offered open loop: each is handed to ``Cluster.submit`` at the
first chance after it falls due, and every time is taken on the host's
``perf_counter`` around the harness's own calls.  A token is *visible*
when the ``Cluster.step()`` that produced it returns; time to first token
runs from the request's due time to that moment.  The program's own
metrics are not read.

Everything the per-layer readers need is kept in a :class:`Record`: the
window, each step's host span with the engine's wall-clock bracket inside
it, the scheduler's plan of each step, each step's expert load, the token
times of every request, and when each request first held a slot.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from bench.workload import Planned


@dataclasses.dataclass
class Step:
    start: float
    end: float
    kind: Optional[str] = None          # "prefill" | "decode" | None (idle)
    bracket_s: float = 0.0              # the engine's WallClock bracket
    plan: object = None                 # the scheduler's plan object
    expert_load: object = None          # (E,) routed rows, summed over layers


@dataclasses.dataclass
class Record:
    t0: float = 0.0                     # window opens (host clock)
    t1: float = 0.0                     # window closes
    steps: List[Step] = dataclasses.field(default_factory=list)
    due: Dict[int, float] = dataclasses.field(default_factory=dict)
    prompt_len: Dict[int, int] = dataclasses.field(default_factory=dict)
    warm: Dict[int, bool] = dataclasses.field(default_factory=dict)
    token_times: Dict[int, List[float]] = dataclasses.field(
        default_factory=dict)
    slot_time: Dict[int, float] = dataclasses.field(default_factory=dict)
    submit_lag: List[float] = dataclasses.field(default_factory=list)
    compiles_before: Dict = dataclasses.field(default_factory=dict)
    compiles_after: Dict = dataclasses.field(default_factory=dict)
    drain_end: float = 0.0


class Driver:
    """Drives one cluster through set-up, the window and the drain."""

    def __init__(self, cluster, engine, clock, compile_counter,
                 annotate=None):
        self.cluster = cluster
        self.eng = engine
        self.clock = clock
        self.compiles = compile_counter
        self.requests: Dict[int, object] = {}
        self.rec = Record()
        self._seen: Dict[int, int] = {}
        self._live: set = set()
        self._recording = False
        self._annotate = annotate or _no_annotation
        self._last_plan = None
        self._loads: List = []
        self._pending_logits: List = []
        # (request id, token index) -> the logits row it was chosen from
        self.logits: Dict = {}
        self._instrument()

    # ------------------------------------------------------------ hooks
    def _instrument(self) -> None:
        """Keep each step's plan, expert load and logits as the program
        hands them over (wrappers on the instance, nothing in the program
        changes), and mark the calls with host spans when tracing."""
        sched, ex = self.eng.scheduler, self.eng.executor
        plan_fn, decode_fn, chunk_fn = (sched.next_plan, ex.decode,
                                        ex.prefill_chunk)

        def next_plan(*a, **kw):
            with self._annotate("sched.next_plan"):
                self._last_plan = plan_fn(*a, **kw)
            return self._last_plan

        def decode(*a, **kw):
            with self._annotate("executor.decode"):
                out = decode_fn(*a, **kw)
            self._loads.append(out[1])
            self._keep_logits(out[0], [
                (b, sched.slots[b]) for b in self._last_plan.slots])
            return out

        def prefill_chunk(*a, **kw):
            with self._annotate("executor.prefill_chunk"):
                out = chunk_fn(*a, **kw)
            self._loads.append(out[1])
            self._keep_prefill_logits(out[0])
            return out

        def prefill(*a, **kw):
            with self._annotate("executor.prefill"):
                out = prefill_fn(*a, **kw)
            self._keep_prefill_logits(out)
            return out

        prefill_fn = ex.prefill
        sched.next_plan, ex.decode, ex.prefill_chunk, ex.prefill = (
            next_plan, decode, prefill_chunk, prefill)

    def _keep_prefill_logits(self, logits) -> None:
        plan = self._last_plan
        if plan.is_last and not plan.request.output_tokens:
            self._keep_logits(logits, [(0, plan.request)])

    def _keep_logits(self, logits, rows) -> None:
        """Keep the logits each served token was chosen from, keyed by
        (request id, token index): the copy to the host starts now and is
        read after the step, so the step waits for nothing extra."""
        logits.copy_to_host_async()
        self._pending_logits.append(
            (logits, [(b, r.request_id, len(r.output_tokens))
                      for b, r in rows]))

    # ------------------------------------------------------------- core
    def submit(self, p: Planned, now: float) -> None:
        from repro.serving import Request, SamplingParams

        req = Request(p.rid, p.prompt, SamplingParams(
            temperature=0.0, max_new_tokens=p.max_new, seed=0),
            arrival_time=p.due)
        self.requests[p.rid] = req
        self.rec.due[p.rid] = self.rec.t0 + p.due
        self.rec.prompt_len[p.rid] = len(p.prompt)
        self.rec.warm[p.rid] = p.warm
        self.rec.token_times[p.rid] = []
        self._seen[p.rid] = 0
        self._live.add(p.rid)
        self.cluster.submit(req)
        if self._recording:
            self.rec.submit_lag.append(now - self.rec.due[p.rid])

    def step(self) -> Step:
        n0 = len(self.clock.brackets)
        self._last_plan, self._loads = None, []
        with self._annotate("bench.step"):
            a = time.perf_counter()
            self.cluster.step()
            b = time.perf_counter()
        st = Step(a, b, plan=self._last_plan)
        for kind, dt in self.clock.brackets[n0:]:
            st.kind, st.bracket_s = kind, st.bracket_s + dt
        if self._loads:
            st.expert_load = self._loads[-1]
        if self._recording:
            self.rec.steps.append(st)
        with self._annotate("bench.observe"):
            self._observe(b)
        return st

    def _observe(self, now: float) -> None:
        for logits, rows in self._pending_logits:
            host = np.asarray(logits)
            for b, rid, idx in rows:
                self.logits[(rid, idx)] = host[b]
        self._pending_logits.clear()
        for b, r in enumerate(self.cluster.slots):
            if r is not None and r.request_id not in self.rec.slot_time:
                self.rec.slot_time[r.request_id] = now
        done = []
        for rid in self._live:
            r = self.requests[rid]
            n = len(r.output_tokens)
            if n > self._seen[rid]:
                self.rec.token_times[rid].extend(
                    [now] * (n - self._seen[rid]))
                self._seen[rid] = n
            if r.finish_time is not None:
                done.append(rid)
        self._live.difference_update(done)
        self.clock.brackets.clear()

    # ----------------------------------------------------------- phases
    def warm(self, planned: List[Planned], decode_steps: int,
             until_done: bool) -> None:
        """Serve the set-up requests: every prompt prefilled, then
        ``decode_steps`` decode steps (or, ``until_done``, to completion)."""
        for p in planned:
            self.submit(p, 0.0)
        decodes = 0
        while self.cluster.has_work():
            st = self.step()
            if until_done:
                continue
            if st.kind == "decode" and all(
                    self.requests[p.rid].output_tokens for p in planned):
                decodes += 1
                if decodes >= decode_steps:
                    break

    def window(self, planned: List[Planned], seconds: float,
               drain_s: float) -> Record:
        """Offer ``planned`` open loop for ``seconds``, then wait up to
        ``drain_s`` for first tokens of requests due in the window."""
        rec = self.rec
        pending = sorted(planned, key=lambda p: p.due)
        i = 0
        rec.compiles_before = self.compiles.snapshot()
        span = self._annotate("bench.window")
        span.__enter__()
        rec.t0 = time.perf_counter()
        rec.t1 = rec.t0 + seconds
        self._recording = True
        while True:
            now = time.perf_counter()
            if now >= rec.t1:
                span.__exit__(None, None, None)
                break
            with self._annotate("bench.submit"):
                while i < len(pending) and rec.t0 + pending[i].due <= now:
                    self.submit(pending[i], now)
                    i += 1
            if not self.cluster.has_work():
                nxt = rec.t0 + pending[i].due if i < len(pending) else rec.t1
                with self._annotate("bench.wait_arrival"):
                    time.sleep(max(0.0, min(nxt, rec.t1) - now))
                continue
            self.step()
        rec.compiles_after = self.compiles.snapshot()
        self._recording = False
        # due before the close but not yet handed over: late, not dropped
        while i < len(pending) and pending[i].due < seconds:
            self.submit(pending[i], time.perf_counter())
            i += 1
        due_in_window = [p.rid for p in pending[:i]]
        deadline = time.perf_counter() + drain_s
        while (drain_s > 0 and time.perf_counter() < deadline
               and any(not rec.token_times[r] for r in due_in_window)
               and self.cluster.has_work()):
            self.step()
        rec.drain_end = time.perf_counter()
        return rec


class _no_annotation:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
