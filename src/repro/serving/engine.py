"""Continuous-batching serving engine — the orchestration layer.

The engine is split in three (the scheduler/executor refactor):

* :class:`~repro.serving.scheduler.Scheduler` — admission, slot
  assignment, chunked prefill and the step policy (what runs next:
  a prefill chunk, a decode step, or idle);
* :class:`~repro.serving.executor.Executor` — params, KV caches and the
  jitted step variants (whole-prompt prefill, chunked-prefill
  continuation, and lockstep / pipelined / serialized decode);
* :class:`ServingEngine` (this module) — wires scheduler → executor →
  metrics around the pluggable :class:`~repro.serving.clock.Clock`, and
  keeps the control plane: failover, rebalancing, elastic ``scale_to``.

One engine class still serves the three system modes (paper §5 baselines):

* ``mode="eaas"``        — EAAS: replicated experts, liveness-masked mapping;
  a server failure re-routes traffic to replicas within the same step
  (throughput dips only by the lost compute share — paper Fig. 10).
* ``mode="monolithic_ep"`` — DeepEP-style: primary-only mapping; a server
  failure halts the WHOLE engine for ``restart_steps`` (the collective-group
  restart) before resuming.
* ``mode="tp"``          — tensor-parallel MoE: failure halts only the
  16-GPU unit (modeled as a shorter stall) but per-unit weight replication
  caps the max batch (``tp_batch_cap``).

The expert→server mapping, liveness mask and local placement table are
**jit arguments**, not compiled constants — failover and rebalancing never
trigger recompilation (the paper's no-group-rebuild property).

Decode can run as two pipelined microbatches (``decode_mode="pipelined"``,
paper §4.2): the expert round-trip of microbatch A overlaps the attention
of microbatch B.  Outputs are bit-identical to the lockstep engine — only
the step cost changes (the overlap-aware
:class:`~repro.serving.clock.VirtualClock` charges ``max(attn, expert)+ε``
instead of the sum; ``decode_mode="serialized"`` is the exposed-collective
ablation).  Chunked prefill (``prefill_chunk=N`` with ``policy="fair"``)
bounds decode gaps to one chunk instead of one prompt.

Execution modes (``exec_mode``): ``lockstep`` (default, bit-identical to
the pre-async engine) advances one synchronous step at a time — every step
blocks on the full expert round-trip.  ``async`` kills that barrier: the
engine computes step *values* eagerly (decode outputs are
batch-composition independent, so values and timing decouple) but posts
their completions onto a discrete-event timeline
(:class:`~repro.serving.clock.EventTimeline`).  A decode wave's expert
share is dispatched as per-server micro-batches into the
:class:`~repro.serving.event_loop.AsyncExpertTier` and the wave completes
when its last micro-batch drains; while the wave's expert phase is in
flight the client is free to run prefill chunks — the overlap lockstep
structurally cannot express.  Same seed ⇒ bitwise-identical per-request
token streams in both modes; only timing (TTFT/ITL/throughput) moves.
"""

from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass
from typing import Callable, Deque, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro import tracing
from repro.configs.base import ModelConfig
from repro.core.elastic import ServerPool
from repro.core.load_balance import lane_loads, server_loads
from repro.core.monitor import Monitor
from repro.models.transformer import build_model
from repro.serving.clock import (Clock, Event, EventTimeline, VirtualClock,
                                 WallClock)
from repro.serving.event_loop import AsyncExpertTier, MicroBatch
from repro.serving.executor import Executor
from repro.serving.kv_pool import BlockPool
from repro.serving.metrics import ServingMetrics
from repro.serving.rebalance import (RebalanceConfig, RebalanceController,
                                     oneshot_rebalance)
from repro.serving.request import Request
from repro.serving.sampling import sample, sample_batch
from repro.serving.scheduler import (DecodeBatch, PrefillChunk, Scheduler,
                                     SchedulerConfig)


@dataclass
class EngineConfig:
    max_batch: int = 8
    max_seq: int = 256
    mode: str = "eaas"                 # eaas | monolithic_ep | tp
    num_servers: int = 4
    n_redundant: int = 2
    restart_steps: int = 50            # monolithic group restart cost
    tp_restart_steps: int = 12         # one TP unit restart
    tp_batch_cap: Optional[int] = None # TP: weight replication caps batch
    # "auto": the Pallas grouped GEMM on TPU, xla_ragged on the CPU
    gemm_impl: str = "auto"
    eos_token: Optional[int] = None
    # --- scheduler knobs -------------------------------------------------
    # max prompt tokens per prefill step (0 = whole prompt, the pre-split
    # behaviour); needs a model family with prefill_chunk support,
    # silently unchunked otherwise
    prefill_chunk: int = 0
    policy: str = "prefill-priority"   # prefill-priority | fair | fcfs
    # --- executor knobs --------------------------------------------------
    # lockstep (pre-split single-batch step) | pipelined (two-microbatch
    # client pipelining, §4.2) | serialized (the ablation: same split,
    # collectives exposed)
    decode_mode: str = "lockstep"
    # lockstep (default: synchronous per-step advancement, bit-identical to
    # the pre-async engine) | async (event-driven expert tier: decode waves
    # dispatch per-server micro-batches whose completions post back through
    # a discrete-event timeline; prefill overlaps in-flight expert phases).
    # async needs mode="eaas" + MoE, kv_mode="dense",
    # decode_mode="lockstep", a VirtualClock and a decoder-family model.
    exec_mode: str = "lockstep"
    # decode waves in flight under exec_mode="async" (depth-K speculative
    # pipelining): wave k+K dispatches on wave k+K-1's eagerly-sampled
    # tokens before the elder combines land, so the client's attention
    # share overlaps up to K expert phases.  1 = strict wave-at-a-time
    # (the cadence then equals lockstep exactly; useful for ablation),
    # 2 = the classic ping-pong double buffer.  Token streams stay
    # bitwise identical to lockstep at every depth — the _slot_exhausted
    # eager done-predicate plus event cancellation keep deep pipelines
    # from running a slot past its final token.
    async_depth: int = 2
    # async-tier queueing discipline: "expert" (default) drains per-expert
    # lanes — a Zipf-hot expert queues only in its own lane while cold
    # co-located experts keep flowing; "server" funnels each server's
    # whole share through one aggregate FIFO (the pre-lane behaviour).
    queue_mode: str = "expert"
    # per-server service-stream budget (queue lanes overlap up to this
    # width on one server).  1 (default) keeps service order — and hence
    # every committed timing — bit-identical to the single-FIFO tier.
    lane_budget: int = 1
    # let the live rebalance controller read the async tier's measured
    # queue backlog: migrations are gated on a modeled queue-delay
    # reduction instead of the routed-count imbalance alone.  No-op under
    # lockstep (there is no tier to observe).
    rebalance_queue_aware: bool = True
    # dispatch-buffer sizing override (tokens per client step); default is
    # max_batch, the seed behaviour — raise it when prefill chunks carry
    # more tokens than a decode batch so fixed-capacity buffers don't drop
    pool_tokens_per_client: Optional[int] = None
    # --- KV-cache knobs --------------------------------------------------
    # dense (per-slot (batch, max_seq) buffers, the seed behaviour) | paged
    # (shared block pool + per-request block tables, prefix caching,
    # memory-aware admission and preemption)
    kv_mode: str = "dense"
    kv_block_size: int = 16
    # pool size in blocks; default sizes the pool so every slot can reach
    # max_seq (no memory pressure) — shrink it to oversubscribe.  Must hold
    # at least one maximal request (max_seq/kv_block_size blocks + scratch)
    # or preemption could not keep the engine live.
    kv_num_blocks: Optional[int] = None
    kv_prefix_cache: bool = True
    # --- live rebalancing knobs ------------------------------------------
    # seconds between live replan evaluations (0 = off, the seed behaviour:
    # placement only changes through explicit rebalance()/scale_to() calls)
    rebalance_interval: float = 0.0
    # expert-weight copies migrated per engine step once a replan is staged
    rebalance_chunk: int = 2
    # relative imbalance improvement required before migrating (hysteresis)
    rebalance_min_gain: float = 0.05
    # post-placement-change quiet period, shared with the autoscaler
    rebalance_cooldown: float = 0.05
    # charge decode steps for hot-expert skew: the expert share of the
    # virtual step cost stretches by the pool's max/mean alive-server load
    # (a lockstep expert phase finishes with its hottest server).  Off by
    # default — existing virtual timelines stay bit-identical.
    charge_imbalance: bool = False
    # relative per-server capacity weights ((num_servers,) or None)
    server_capacities: Optional[np.ndarray] = None
    # feed chunked-prefill router traffic into the expert-load EMA (decode
    # steps always feed it); prompt-heavy workloads then trigger rebalances
    # from prefill pressure, not only after decoding starts
    prefill_load_feedback: bool = True


def init_params(cfg: ModelConfig, num_servers: int, redundant_table,
                key: jax.Array):
    """The served model's params from one jitted init placed on the
    default device.  Run eagerly, every intermediate (f32 draws before the
    bf16 cast, the per-server gather of each expert bank) would be
    materialised at once — several times the jitted peak at real widths."""
    red = None if redundant_table is None else tuple(
        map(tuple, np.asarray(redundant_table).tolist()))
    return _jitted_init(cfg, num_servers, red)(key)


@functools.lru_cache(maxsize=8)
def _jitted_init(cfg: ModelConfig, num_servers: int,
                 red: Optional[Tuple[Tuple[int, ...], ...]]):
    # one compile per (config, pool shape) — engines of a test or a cluster
    # share it
    table = None if red is None else np.asarray(red, np.int32).reshape(
        num_servers, -1)
    model = build_model(cfg, num_servers=num_servers, redundant_table=table)
    return jax.jit(model.init_params,
                   out_shardings=SingleDeviceSharding(jax.devices()[0]))


class ServingEngine:
    """Scheduler → executor → metrics orchestrator with EAAS failover.

    Standalone this is one complete serving system; under a
    :class:`~repro.serving.cluster.Cluster` it is one *attention client* of
    N — the cluster injects the shared expert-tier ``pool`` (usually a
    per-client :class:`~repro.core.elastic.PoolClient` mapping view) and
    owns the placement control plane (rebalance / scale), while each client
    keeps its own scheduler, executor, KV pool and clock.
    """

    def __init__(self, cfg: ModelConfig, engine_cfg: EngineConfig,
                 params=None, seed: int = 0, clock: Optional[Clock] = None,
                 pool=None, client_id: int = 0, tier=None):
        tracing.watch_compiles()
        self.cfg = cfg
        self.ecfg = engine_cfg
        self.client_id = client_id
        self.clk = clock if clock is not None else WallClock()
        if engine_cfg.exec_mode not in ("lockstep", "async"):
            raise ValueError(
                f"unknown exec_mode {engine_cfg.exec_mode!r}; expected "
                "'lockstep' or 'async'")
        if engine_cfg.exec_mode == "async":
            if engine_cfg.mode != "eaas" or not cfg.moe:
                raise ValueError(
                    "exec_mode='async' models the EAAS expert tier — it "
                    "needs mode='eaas' and an MoE config")
            if engine_cfg.kv_mode != "dense":
                raise ValueError(
                    "exec_mode='async' supports kv_mode='dense' only "
                    "(paged preemption is defined against the lockstep "
                    "step loop)")
            if engine_cfg.decode_mode != "lockstep":
                raise ValueError(
                    "exec_mode='async' overlaps at the wave level — "
                    "decode_mode must stay 'lockstep'")
            if not isinstance(self.clk, VirtualClock):
                raise ValueError(
                    "exec_mode='async' needs a VirtualClock: the event "
                    "timeline is a deterministic modeled-cost timeline")
            if (not isinstance(engine_cfg.async_depth, (int, np.integer))
                    or engine_cfg.async_depth < 1):
                raise ValueError(
                    f"async_depth must be an integer >= 1, got "
                    f"{engine_cfg.async_depth!r}")
            if engine_cfg.queue_mode not in ("expert", "server"):
                raise ValueError(
                    f"unknown queue_mode {engine_cfg.queue_mode!r}; "
                    "expected 'expert' or 'server'")
            if engine_cfg.lane_budget < 1:
                raise ValueError(
                    f"lane_budget must be >= 1, got "
                    f"{engine_cfg.lane_budget}")
        S = engine_cfg.num_servers if engine_cfg.mode != "tp" else 1
        # pool injected = cluster member: the expert tier is shared, its
        # placement is the cluster's to change (scale_to/rebalance here
        # would desync the sibling clients' executors)
        self._shared_pool = pool is not None
        if self._shared_pool:
            if not cfg.moe:
                raise ValueError("shared expert pool needs an MoE config")
            if engine_cfg.mode == "tp":
                raise ValueError("tp mode replicates expert weights per "
                                 "unit — it has no shared expert tier")
            self.pool = pool
            S = pool.num_servers
        elif cfg.moe:
            self.pool = ServerPool(
                cfg, S,
                tokens_per_client=(engine_cfg.pool_tokens_per_client
                                   or engine_cfg.max_batch),
                n_redundant=(engine_cfg.n_redundant
                             if engine_cfg.mode == "eaas" else 0),
                capacities=engine_cfg.server_capacities)
        else:
            self.pool = None
        self.model = build_model(
            cfg, num_servers=S if cfg.moe else 1,
            redundant_table=self.pool.redundant_table if self.pool else None)
        if engine_cfg.exec_mode == "async" \
                and self.model.cache_batch_axis is None:
            raise ValueError(
                "exec_mode='async' needs a model family with a uniform "
                "cache batch axis (decoder family) — wave decodes mask "
                "inactive slot rows")
        if params is None:
            params = init_params(cfg, S if cfg.moe else 1,
                                 self.pool.redundant_table
                                 if self.pool else None,
                                 jax.random.PRNGKey(seed))
        self.monitor = Monitor(heartbeat_timeout=3.0)
        if self.pool:
            self.monitor.subscribe_server_down(self.pool.server_failed)

        self.kv_pool: Optional[BlockPool] = None
        if engine_cfg.kv_mode == "paged":
            bs = engine_cfg.kv_block_size
            if engine_cfg.max_seq % bs:
                raise ValueError(f"max_seq={engine_cfg.max_seq} must be a "
                                 f"multiple of kv_block_size={bs}")
            per_seq = engine_cfg.max_seq // bs
            nb = (engine_cfg.kv_num_blocks
                  if engine_cfg.kv_num_blocks is not None
                  else engine_cfg.max_batch * per_seq + 1)
            if nb - 1 < per_seq:
                raise ValueError(
                    f"kv_num_blocks={nb} cannot hold one maximal request "
                    f"({per_seq} blocks + 1 scratch) — preemption could "
                    "not keep the engine live")
            self.kv_pool = BlockPool(
                nb, bs, enable_prefix_cache=engine_cfg.kv_prefix_cache)
        self.executor = Executor(
            self.model, params, self.pool,
            max_batch=engine_cfg.max_batch, max_seq=engine_cfg.max_seq,
            gemm_impl=engine_cfg.gemm_impl,
            decode_mode=engine_cfg.decode_mode,
            kv_mode=engine_cfg.kv_mode,
            kv_block_size=engine_cfg.kv_block_size,
            kv_num_blocks=(self.kv_pool.num_blocks if self.kv_pool else 0))
        chunk = (engine_cfg.prefill_chunk
                 if self.executor.supports_chunked_prefill else 0)
        # with chunking on, a one-chunk prompt runs the chunk program too:
        # the whole-prompt one compiles for each prompt length
        self._chunked = chunk > 0
        self.scheduler = Scheduler(SchedulerConfig(
            max_batch=engine_cfg.max_batch, prefill_chunk=chunk,
            policy=engine_cfg.policy,
            batch_cap=(engine_cfg.tp_batch_cap
                       if engine_cfg.mode == "tp" else None),
            max_seq=engine_cfg.max_seq), kv_pool=self.kv_pool)

        self.metrics = ServingMetrics()
        self.step_idx = 0
        self.clock = 0.0
        self.halted_until = -1
        self._last_decode_time = 0.01
        # per-server straggler factors (scenario slow_server): lockstep
        # charges the max alive factor as an expert-share stretch; the
        # async tier applies them per micro-batch queue
        self.server_speed = np.ones(self._pool_size())
        # --- async exec state -------------------------------------------
        self.timeline = EventTimeline()
        self.tier: Optional[AsyncExpertTier] = None
        self._client_free_at = 0.0       # attention client busy-until
        # in-flight decode waves, FIFO in dispatch order (completion is
        # FIFO too: combine is in-order, so a younger wave that drains
        # early waits for its elders)
        self._waves: Deque[dict] = deque()
        self._wave_counter = 0
        # pending mb_done completion events by micro-batch id: superseded
        # events (failure re-dispatch, reconcile after a lost server) are
        # cancelled on the timeline outright — generation staleness stays
        # as the second guard — so a depth-K pipeline never accumulates
        # dead events
        self._mb_events: dict = {}
        if engine_cfg.exec_mode == "async":
            # a cluster injects the shared tier; standalone owns its own
            self.tier = tier if tier is not None else AsyncExpertTier(
                S, queue_mode=engine_cfg.queue_mode,
                lane_budget=engine_cfg.lane_budget)
        # attention clients currently sharing the expert tier (the cluster
        # sets this before each member step; 1.0 = standalone engine, and
        # the virtual cost model is bit-identical to the pre-cluster one)
        self.expert_contention = 1.0
        # compute/surface the pool imbalance gauge each decode step; set
        # below for a local controller, and by the Cluster on its member
        # clients when the CLUSTER-level controller is active
        self.track_imbalance = False
        # shared placement cooldown (rebalance commits + elastic scaling)
        self.last_placement_change = float("-inf")
        self.rebalancer: Optional[RebalanceController] = None
        if (engine_cfg.rebalance_interval > 0 and self.pool is not None
                and not self._shared_pool
                and engine_cfg.mode == "eaas"):
            self.rebalancer = RebalanceController(RebalanceConfig(
                interval=engine_cfg.rebalance_interval,
                chunk=engine_cfg.rebalance_chunk,
                min_gain=engine_cfg.rebalance_min_gain,
                cooldown=engine_cfg.rebalance_cooldown,
                queue_aware=engine_cfg.rebalance_queue_aware))
        self.track_imbalance = self.rebalancer is not None

    # ------------------------------------------------- back-compat surface
    @property
    def queue(self):
        return self.scheduler.queue

    @property
    def slots(self):
        return self.scheduler.slots

    @property
    def params(self):
        return self.executor.params

    @property
    def cache(self):
        return self.executor.cache

    # ------------------------------------------------------------ helpers
    def _alive_frac(self) -> float:
        """Alive share of the expert-server pool (EAAS failover slowdown)."""
        if self.pool is None or self.ecfg.mode != "eaas":
            return 1.0
        return float(self.pool.smap.alive.mean())

    def _pool_size(self) -> int:
        return self.pool.num_servers if self.pool else 1

    def _alive_mask(self) -> np.ndarray:
        """This client's view of server liveness ((S,) bool)."""
        if self.pool is None:
            return np.ones(1, bool)
        if hasattr(self.pool, "alive_mask"):
            return np.asarray(self.pool.alive_mask(), bool)
        return np.asarray(self.pool.smap.alive, bool)

    def _straggle(self) -> float:
        """Slowdown factor of the slowest *alive* expert server — a
        lockstep expert phase finishes with its slowest server."""
        if self.pool is None or self.ecfg.mode != "eaas":
            return 1.0
        alive = self._alive_mask()
        n = min(len(alive), len(self.server_speed))
        sp = self.server_speed[:n][alive[:n]]
        return float(sp.max()) if sp.size else 1.0

    # --------------------------------------------------- front-end signals
    def pending_prefill_tokens(self) -> int:
        """Unprefilled prompt tokens (queued + mid-chunk) — the autoscaler
        and the least-loaded front-end policy read this."""
        return self.scheduler.pending_prefill_tokens()

    def kv_free_fraction(self) -> float:
        return self.scheduler.kv_free_fraction()

    def queue_signals(self) -> Optional[dict]:
        """Live async-tier queue signals (per-server backlog seconds, the
        per-lane depth/backlog breakdown) at the current engine clock —
        what the queue-aware rebalance gate reads.  None under lockstep:
        there is no tier to observe."""
        if self.tier is None:
            return None
        return self.tier.queue_signals(self.clock)

    def free_kv_tokens(self) -> int:
        """Token capacity this client can still admit into: free pool
        blocks (paged) or free slots × max_seq (dense) — the memory half of
        the least-loaded routing score."""
        if self.kv_pool is not None:
            return self.kv_pool.available() * self.kv_pool.block_size
        free_slots = sum(1 for s in self.slots if s is None)
        return free_slots * self.ecfg.max_seq

    def abort_inflight(self) -> list:
        """Drop every queued and in-flight request (client failure): slots
        and KV blocks are released, nothing is re-queued.  Returns the
        stranded requests — the cluster counts them as failed.  The expert
        tier is untouched; sibling clients keep serving."""
        stranded = list(self.scheduler.queue)
        self.scheduler.queue.clear()
        for b, r in enumerate(self.scheduler.slots):
            if r is not None:
                stranded.append(r)
                self.scheduler.release(b)
        self.executor._staging.clear()
        if self.ecfg.exec_mode == "async":
            # strand only this client's queued tier work: its in-flight
            # micro-batches are abandoned (the servers finish the already
            # dispatched compute — occupancy stays — and discard results);
            # sibling clients' queues are untouched
            if self.tier is not None:
                self.tier.cancel_client(self.client_id)
            self.timeline.clear_pending()
            self._waves.clear()
            self._mb_events.clear()
            self._client_free_at = self.clock
        return stranded

    # ------------------------------------------------------------- control
    def submit(self, req: Request) -> None:
        self.scheduler.submit(req)
        self.metrics.total_requests += 1

    def set_policy(self, policy: str) -> None:
        """Switch the scheduler policy mid-run (scenario ``set_policy``)."""
        self.scheduler.set_policy(policy)
        self.metrics.events.append(
            {"t": self.clock, "event": "set_policy", "policy": policy})

    def inject_server_failure(self, rank: int) -> None:
        """Simulated hardware failure of one expert server (paper §5.4)."""
        self.metrics.events.append(
            {"t": self.clock, "event": "server_fail", "rank": rank,
             "mode": self.ecfg.mode})
        if self.ecfg.mode == "eaas":
            if self.pool and rank < self.pool.num_servers:
                self.pool.server_failed(rank)     # mapping mask update only
            if self.tier is not None:
                # re-dispatch the dead server's queued micro-batches to
                # survivors: fresh completion events from the new finish
                # times; the old events are stale by generation
                moved = self.tier.fail_server(rank, self.clock)
                for mb in moved:
                    self._post_redispatch(mb)
                if moved:
                    self.metrics.events.append(
                        {"t": self.clock, "event": "redispatch",
                         "rank": rank, "count": len(moved)})
                self._reconcile_waves()
        elif self.ecfg.mode == "monolithic_ep":
            self.halted_until = self.step_idx + self.ecfg.restart_steps
        elif self.ecfg.mode == "tp":
            self.halted_until = self.step_idx + self.ecfg.tp_restart_steps

    def recover_server(self, rank: int) -> None:
        self.metrics.events.append(
            {"t": self.clock, "event": "server_recover", "rank": rank})
        if self.pool and rank < self.pool.num_servers:
            self.pool.server_recovered(rank)
        if self.tier is not None and rank < self.tier.num_servers:
            self.tier.recover_server(rank, self.clock)

    def set_server_speed(self, rank: int, factor: float) -> None:
        """Mark expert server ``rank`` as running ``factor``× slower
        (scenario ``slow_server``; 1.0 restores full speed).  Lockstep
        charges every decode step the max alive factor — the whole tier
        waits for its slowest server; the async tier slows only that
        server's micro-batch queue, which is exactly the tail-latency
        asymmetry the differential tests pin."""
        if self.pool is None or rank >= len(self.server_speed):
            return
        if factor <= 0:
            raise ValueError(f"server speed factor must be > 0: {factor}")
        self.server_speed[rank] = float(factor)
        if self.tier is not None and rank < self.tier.num_servers:
            self.tier.set_slowdown(rank, factor)
        self.metrics.events.append(
            {"t": self.clock, "event": "slow_server", "rank": rank,
             "factor": float(factor)})

    def apply_migration(self, copies) -> None:
        """Apply one expert-weight migration chunk to this engine's
        executor.  A :class:`~repro.serving.cluster.Cluster` overrides the
        *host* side of this call to fan the same copies out to every
        client's executor — replica weights never diverge across clients."""
        self.executor.migrate_slots(copies)

    def charge_migration(self, dt: float) -> None:
        """Advance the engine clock by a migration chunk's cost.  The
        cluster version charges every client — the shared expert tier is
        busy copying weights, so everyone's next expert phase waits.

        Under ``exec_mode='async'`` the copy occupies the *expert tier*
        instead: in-flight micro-batches keep their committed finish
        times, subsequent dispatches queue behind the copy, and the client
        keeps running attention/prefill — migration chunks interleave with
        in-flight work rather than stalling the world."""
        if self.tier is not None:
            self.tier.occupy_all(self.clock, dt)
            return
        self.clock += dt

    def page_out_experts(self, experts) -> list:
        """Scale-to-zero: page cold experts out of the expert tier (their
        replica bank slots are zeroed through the migration weight path,
        the mapping keeps only the primary as the page-in source).  An
        expert with in-flight work on its tier lanes is skipped this round
        — eviction waits for the lanes to drain, never cancels them.  The
        first token later routed to a paged-out expert pages it back in
        and pays the clock's ``cold_start_base`` penalty.  Returns the
        experts actually paged out."""
        if self.pool is None:
            return []
        if self._shared_pool:
            raise RuntimeError(
                "this engine is a cluster client over a shared expert "
                "tier — call Cluster.page_out_experts() so every client's "
                "executor evicts in lockstep")
        ready = [e for e in experts
                 if self.tier is None or not self.tier.expert_in_flight(e)]
        paged, updates = self.pool.page_out_experts(ready)
        if updates:
            self.apply_migration(updates)
        if paged:
            self.last_placement_change = self.clock
            self.metrics.expert_page_outs += len(paged)
            self.metrics.events.append(
                {"t": self.clock, "event": "page_out",
                 "experts": len(paged)})
        return paged

    def _charge_cold_starts(self, expert_load) -> float:
        """Page in every cold expert this step's routed load touched and
        return the modeled stall (``cold_start_base`` per expert; 0.0 —
        the default — keeps elastic timelines bit-identical to non-elastic
        ones).  Values never depend on residency: the primary shard stayed
        addressable, so the tokens already computed exactly — only time
        passes here."""
        pool = self.pool
        if pool is None:
            return 0.0
        cold = getattr(pool, "cold", None)
        if not cold:
            return 0.0
        load = np.asarray(expert_load)
        hits = sorted(e for e in cold if e < load.shape[0] and load[e] > 0)
        if not hits:
            return 0.0
        for e in hits:
            pool.page_in_expert(e, self.clock)
        self.clk.start()
        dt = self.clk.stop("cold_start", tokens=len(hits))
        self.metrics.cold_starts += len(hits)
        self.metrics.cold_start_time += dt
        self.metrics.events.append(
            {"t": self.clock, "event": "cold_start", "experts": len(hits),
             "dt": dt})
        return dt

    def rebalance(self) -> None:
        """One-shot EPLB replica re-planning from live traffic (paper
        §4.5) — the scripted/manual path.  Placement-identical plans are
        skipped via ``plan_digest`` (nothing rebuilt); a changed plan
        migrates the replica weights *and* the mapping in one step (the
        weight copies charged as one big ``migrate`` step), so weights and
        local table never disagree.  The live ``rebalance_interval``
        controller spreads the same work over chunked migration steps
        interleaved with decoding instead.
        """
        if self.pool is None:
            return
        if self._shared_pool:
            raise RuntimeError(
                "this engine is a cluster client over a shared expert "
                "tier — call Cluster.rebalance() so every client's "
                "executor migrates in lockstep")
        if self.rebalancer is not None:
            self.rebalancer.abort()      # the one-shot replan supersedes it
        oneshot_rebalance(self)

    def set_skew(self, bias: np.ndarray) -> None:
        """Install a router-logit bias (scenario ``set_skew`` traffic
        shaping).  Pure runtime data — the next jitted step routes under
        the new bias without recompiling."""
        if self.pool is None:
            return
        self.pool.set_route_bias(bias)
        bias = np.asarray(bias, np.float64)
        self.metrics.events.append(
            {"t": self.clock, "event": "set_skew",
             "spread": round(float(bias.max() - bias.min()), 6)})

    def scale_to(self, n: int) -> None:
        """Elastically resize the expert-server pool to ``n`` servers.

        The pool re-plans its EPLB mapping (liveness preserved), the
        executor re-shards the expert weights from the recovered global bank
        and rebuilds its jitted variants for the new static server count
        (the AOT-per-server-count story).  In-flight requests keep their KV
        cache — scaling never drops work (paper §5.3).
        """
        if self.pool is None or n == self.pool.num_servers:
            return
        if self._shared_pool:
            raise RuntimeError(
                "this engine is a cluster client over a shared expert "
                "tier — call Cluster.scale_to() so every client's "
                "executor re-shards in lockstep")
        old = self.pool.num_servers
        if self.rebalancer is not None:
            self.rebalancer.abort()      # a resize replans placement anyway
        self._drain_async()              # quiesce in-flight waves first
        self.pool.scale_to(n)
        self.executor.resize(self.pool)
        self.server_speed = np.ones(n)   # fresh pool, fresh speeds
        if self.tier is not None:
            # _drain_async quiesced the waves, so a standalone resize has
            # nothing in flight — but the reconcile contract holds anyway:
            # work still queued on dropped ranks re-dispatches and its
            # completion events are re-posted
            for mb in self.tier.resize(n, self.clock):
                self._post_redispatch(mb)
            self._reconcile_waves()
            self.tier.reset_speeds()     # match the server_speed reset
        self.last_placement_change = self.clock
        self.metrics.events.append(
            {"t": self.clock, "event": "scale", "from": old, "to": n})

    # ---------------------------------------------------------------- step
    def step(self) -> None:
        """One engine iteration: run whatever the scheduler plans next —
        a prefill chunk, a decode step over the ready slots, or idle.  It
        is the ``engine.step`` span (:mod:`repro.tracing`), numbered by
        ``step_idx``, its ``kind`` the step it ran."""
        self.step_idx += 1
        with tracing.step("engine.step", self.step_idx) as span:
            if self.step_idx <= self.halted_until:
                # monolithic restart: time passes, no tokens are produced
                span.set(kind="idle")
                self.clock += self._last_decode_time
                self.metrics.timeline.append(
                    {"t": self.clock, "tokens": 0, "halted": True})
                return
            if self.ecfg.exec_mode == "async":
                kind = self._step_async()
            else:
                plan = self.scheduler.next_plan()
                kind = "idle"
                if isinstance(plan, PrefillChunk):
                    kind = "prefill"
                    self._step_prefill(plan)
                elif isinstance(plan, DecodeBatch):
                    kind = "decode"
                    self._step_decode(plan)
                else:
                    self.clock += self.clk.idle()
            span.set(kind=kind)
            if self.rebalancer is not None:
                # migration chunks interleave with decode steps — serving
                # never pauses for a replan (paper §4.5 live adaptation)
                self.rebalancer.step(self)
            if self.kv_pool is not None:
                self.metrics.observe_kv(self.kv_pool,
                                        self.scheduler.preemptions)

    def _sample(self, logits, temps: np.ndarray,
                steps: np.ndarray) -> np.ndarray:
        """Every slot's next token, on the host (``executor.sample``; the
        pull of the tokens is ``executor.wait``)."""
        with tracing.span("executor.sample"):
            tokens = sample_batch(logits, temps, self.scheduler.slot_keys,
                                  steps)
            with tracing.span("executor.wait"):
                return np.asarray(tokens)

    def _sample_first(self, logits, req: Request, b: int) -> int:
        """The first token of ``req`` in slot ``b``, from its prefill's
        logits: the per-slot key the decode path uses (stored at
        admission), folded with token index 0 — one key-derivation site."""
        key = jnp.asarray(self.scheduler.slot_keys[b])
        token = sample(logits, req.sampling.temperature,
                       jax.random.fold_in(key, 0))
        with tracing.span("executor.wait"):
            return int(token[0])

    def _step_prefill(self, plan: PrefillChunk) -> None:
        req, b = plan.request, plan.slot
        chunk = (plan.tokens if plan.tokens is not None
                 else req.prompt[plan.start:plan.start + plan.length])
        self.clk.start()
        expert_load = None
        if self.kv_pool is not None:
            # paged: every prefill runs the chunk path against the block
            # pool (prefix hits start mid-prompt; the virtual clock is
            # charged only the uncached tokens in ``plan.length``)
            self.executor.copy_blocks(plan.copies)     # pending COW forks
            logits, expert_load = self.executor.prefill_chunk_paged(
                chunk, plan.start, self.scheduler.block_tables[b])
        elif plan.is_first and plan.is_last and not self._chunked:
            # whole prompt in one step — the pre-split prefill path
            logits = self.executor.prefill(b, chunk)
        else:
            logits, expert_load = self.executor.prefill_chunk(
                b, chunk, plan.start,
                is_first=plan.is_first, is_last=plan.is_last)
        load = self.executor.pull_load(expert_load)
        if load is not None and self.ecfg.prefill_load_feedback:
            # chunked-prefill router traffic feeds the same EMA decode
            # feeds — prompt-heavy workloads rebalance from prompt traffic
            self.pool.observe_load(load)
        with tracing.span("executor.wait"):
            self.clock += self.clk.stop("prefill", result=logits,
                                        tokens=plan.length,
                                        servers=self._pool_size(),
                                        alive_frac=self._alive_frac())
        if load is not None:
            self.clock += self._charge_cold_starts(load)
        self.scheduler.prefill_advanced(b, plan.length)
        if plan.is_last and not req.output_tokens:
            # a *resumed* (preempted) request already holds its next input
            # token, so recompute prefills skip sampling and TTFT
            first = self._sample_first(logits, req, b)
            req.output_tokens.append(first)
            req.prefill_time = self.clock
            self.metrics.ttfts.append(self.clock - req.arrival_time)
            self.metrics.events.append(
                {"t": self.clock, "event": "prefill", "rid": req.request_id,
                 "ttft": self.clock - req.arrival_time})

    def _step_decode(self, plan: DecodeBatch) -> None:
        sch = self.scheduler
        B = len(sch.slots)
        active = list(plan.slots)
        tokens = np.zeros((B, 1), np.int32)
        temps = np.zeros(B, np.float32)
        steps = np.zeros(B, np.int32)
        for b in active:
            r = sch.slots[b]
            tokens[b, 0] = r.output_tokens[-1]
            temps[b] = r.sampling.temperature
            steps[b] = len(r.output_tokens)
        self.clk.start()
        if self.kv_pool is not None:
            logits, expert_load = self.executor.decode_paged(
                tokens, self.scheduler.block_tables,
                self.scheduler.cache_lengths())
        else:
            logits, expert_load = self.executor.decode(tokens)
        imbalance = 1.0
        load = self.executor.pull_load(expert_load)
        if load is not None:
            # fold this step's router traffic into the EMA first, so the
            # imbalance charged (and surfaced) reflects current traffic;
            # the gauge itself is only computed when something consumes it
            # (cost model or controller) — it walks the mapping in Python
            self.pool.observe_load(load)
            if self.ecfg.charge_imbalance or self.track_imbalance:
                imbalance = self.pool.current_imbalance()
                self.metrics.observe_balance(imbalance)
        with tracing.span("executor.wait"):
            dt = self.clk.stop("decode", result=logits, tokens=len(active),
                               servers=self._pool_size(),
                               alive_frac=self._alive_frac(),
                               overlap=(self.ecfg.decode_mode == "pipelined"),
                               imbalance=(imbalance
                                          if self.ecfg.charge_imbalance
                                          else 1.0),
                               contention=self.expert_contention,
                               straggle=self._straggle())
        self._last_decode_time = dt
        self.clock += dt
        self.clock += self._charge_cold_starts(load)
        next_tokens = self._sample(logits, temps, steps)

        produced = 0
        for b in active:
            r = sch.slots[b]
            tok = int(next_tokens[b])
            r.output_tokens.append(tok)
            r.token_times.append(self.clock)
            produced += 1
            self.metrics.total_output_tokens += 1
            done = (len(r.output_tokens) >= r.sampling.max_new_tokens or
                    (self.ecfg.eos_token is not None and
                     tok == self.ecfg.eos_token) or
                    len(r.prompt) + len(r.output_tokens) >=
                    self.ecfg.max_seq - 1)
            if done:
                r.finish_time = self.clock
                self.metrics.completed += 1
                self.metrics.itls.extend(r.itl())
                sch.release(b)
        self.metrics.timeline.append(
            {"t": self.clock, "tokens": produced, "halted": False})

    # --------------------------------------------------------- async steps
    def _step_async(self) -> str:
        """One event-driven iteration; returns what it ran (``prefill``,
        ``decode`` or ``idle``: an event handled, or time passed).

        If the attention client is free, plan eagerly: a prefill chunk
        runs now (overlapping any in-flight wave's expert phase), and a
        decode wave dispatches its expert share into the tier as long as
        fewer than ``async_depth`` waves are in flight — wave k+1 runs on
        wave k's eagerly-sampled tokens before k's combine lands (ping-pong
        double buffering), so the client's attention share and the tier's
        expert share overlap instead of summing.  Otherwise advance the
        clock to the earlier of the next timeline event and the client's
        busy-until, handling the event if that's what comes first.
        """
        if self.clock >= self._client_free_at:
            plan = self.scheduler.next_plan()
            if isinstance(plan, PrefillChunk):
                self._async_prefill(plan)
                return "prefill"
            if (isinstance(plan, DecodeBatch)
                    and len(self._waves) < self.ecfg.async_depth
                    and self._async_decode(plan)):
                return "decode"
        ev_t = self.timeline.peek_time()
        free_t = (self._client_free_at
                  if self._client_free_at > self.clock else None)
        if ev_t is not None and (free_t is None or ev_t <= free_t):
            ev = self.timeline.pop()
            self.clock = max(self.clock, ev.time)
            self._handle_event(ev)
        elif free_t is not None:
            self.clock = free_t
        else:
            self.clock += self.clk.idle()
        return "idle"

    def _async_prefill(self, plan: PrefillChunk) -> None:
        """Run one prefill chunk eagerly; its completion (scheduler
        progress, first-token sampling, TTFT) lands at event time.  Values
        are computed now — they don't depend on when the chunk finishes —
        so the event handler only does bookkeeping."""
        req, b = plan.request, plan.slot
        chunk = (plan.tokens if plan.tokens is not None
                 else req.prompt[plan.start:plan.start + plan.length])
        self.clk.start()
        expert_load = None
        if plan.is_first and plan.is_last and not self._chunked:
            logits = self.executor.prefill(b, chunk)
        else:
            logits, expert_load = self.executor.prefill_chunk(
                b, chunk, plan.start,
                is_first=plan.is_first, is_last=plan.is_last)
        load = self.executor.pull_load(expert_load)
        if load is not None and self.ecfg.prefill_load_feedback:
            self.pool.observe_load(load)
        with tracing.span("executor.wait"):
            dt = self.clk.stop("prefill", result=logits, tokens=plan.length,
                               servers=self._pool_size(),
                               alive_frac=self._alive_frac())
        if load is not None:
            dt += self._charge_cold_starts(load)
        first = None
        if plan.is_last and not req.output_tokens:
            first = self._sample_first(logits, req, b)
        t_done = self.clock + dt
        self._client_free_at = t_done
        self.timeline.post(t_done, "prefill_done", slot=b,
                           rid=req.request_id, length=plan.length,
                           last=plan.is_last, first=first, req=req)

    def _slot_pending(self, b: int) -> list:
        """Tokens sampled for slot ``b`` by in-flight waves, oldest first —
        computed eagerly at dispatch but not yet appended (that happens at
        each wave's completion event)."""
        return [int(w["tokens"][b]) for w in self._waves
                if b in w["slot_set"]]

    def _slot_exhausted(self, b: int, r: Request) -> bool:
        """Counting in-flight sampled tokens, will slot ``b`` be done when
        its last wave lands?  Mirrors the lockstep done-check exactly, so
        a slot is never dispatched past its final token even though that
        token hasn't been committed yet.

        A slot with *no* wave in flight is never exhausted: lockstep
        always decodes a ready slot and runs the done check only after
        appending — even when the prefill-sampled first token already
        meets the done condition (max_new_tokens=1, or EOS sampled at
        prefill) it decodes exactly once more and releases at the check.
        Holding a pend-empty slot instead would park it forever: with no
        wave in flight there is no completion event left to release it,
        and its token stream would diverge from lockstep's."""
        pend = self._slot_pending(b)
        if not pend:
            return False
        count = len(r.output_tokens) + len(pend)
        return (count >= r.sampling.max_new_tokens
                or (self.ecfg.eos_token is not None
                    and pend[-1] == self.ecfg.eos_token)
                or len(r.prompt) + count >= self.ecfg.max_seq - 1)

    def _async_decode(self, plan: DecodeBatch) -> bool:
        """Dispatch one decode wave: compute values eagerly (masked so
        non-wave slot rows stay resumable), split the step cost into the
        client share (attention/dispatch/combine — the client is busy for
        it) and the expert share, and enqueue the expert share as
        per-server micro-batches.  A slot whose previous token is still
        in flight decodes on the eagerly-sampled value — values never wait
        for events — while completed-token bookkeeping (append, ITL,
        release) stays at event time.  Returns False when every offered
        slot is already exhausted (nothing dispatched)."""
        sch = self.scheduler
        B = len(sch.slots)
        active = []
        for b in plan.slots:
            r = sch.slots[b]
            if self._slot_exhausted(b, r):
                # park it until its final (in-flight, _slot_exhausted
                # guarantees one) wave's completion releases it
                sch.hold(b)
            else:
                active.append(b)
        if not active:
            return False
        tokens = np.zeros((B, 1), np.int32)
        temps = np.zeros(B, np.float32)
        steps = np.zeros(B, np.int32)
        mask = np.zeros(B, bool)
        for b in active:
            r = sch.slots[b]
            pend = self._slot_pending(b)
            tokens[b, 0] = pend[-1] if pend else r.output_tokens[-1]
            temps[b] = r.sampling.temperature
            steps[b] = len(r.output_tokens) + len(pend)
            mask[b] = True
        self.clk.start()
        logits, expert_load = self.executor.decode_masked(tokens, mask)
        load = self.executor.pull_load(expert_load)
        if load is not None:
            self.pool.observe_load(load)
            if self.ecfg.charge_imbalance or self.track_imbalance:
                self.metrics.observe_balance(self.pool.current_imbalance())
        next_tokens = self._sample(logits, temps, steps)
        S = self._pool_size()
        af = self._alive_frac()
        client_dt, expert_dt = self.clk.decode_split(
            tokens=len(active), servers=S, alive_frac=af)
        # a wave routing to paged-out experts stalls its own dispatch on
        # the page-in: the client share absorbs the cold-start penalty
        client_dt += self._charge_cold_starts(load)
        t_dispatch = self.clock + client_dt
        self._client_free_at = t_dispatch
        wave_id = self._wave_counter
        self._wave_counter += 1
        entries = self._wave_lane_entries(
            np.asarray(load, np.float64), S, expert_dt)
        wave = {"id": wave_id, "slots": active, "slot_set": set(active),
                "tokens": next_tokens, "pending": set()}
        self._waves.append(wave)
        if not entries:
            # no alive server / no routed-load signal (all-dead pool
            # edge): one aggregate completion at the analytic stretched
            # cost; the sentinel keeps the wave pending until it fires
            wave["pending"].add("wave")
            self.timeline.post(t_dispatch + expert_dt / max(af, 1e-3),
                               "wave_done", wave=wave_id)
        else:
            mbs = self.tier.dispatch_lanes(self.client_id, wave_id,
                                           entries, now=t_dispatch)
            for mb in mbs:
                wave["pending"].add(mb.mb_id)
                self._mb_events[mb.mb_id] = self.timeline.post(
                    mb.finish_t, "mb_done", mb=mb.mb_id,
                    gen=mb.generation, wave=wave_id, server=mb.server,
                    expert=mb.expert)
            if not mbs:
                wave["pending"].add("wave")
                self.timeline.post(t_dispatch, "wave_done", wave=wave_id)
        return True

    def _wave_lane_entries(self, expert_load: np.ndarray, S: int,
                           expert_dt: float) -> list:
        """Decompose one wave's expert share into tier dispatch entries
        ``(server, expert, work_seconds, tokens)``.

        Per-server totals: ``expert_dt`` is the perfectly-balanced
        per-server time; by default each alive server gets the uniform
        share ``expert_dt * S / alive`` (dead servers' work concentrates
        on survivors — the 1/alive_frac stretch, reproduced physically as
        queueing).  With ``charge_imbalance`` the shares follow this
        step's *real* routed load instead, mirroring the lockstep clock's
        analytic imbalance stretch.

        Under ``queue_mode="expert"`` each server's share splits further
        into per-expert lane entries along the routed-load decomposition
        (:func:`~repro.core.load_balance.lane_loads`) — same per-server
        totals, finer queueing granularity — emitted server-major,
        expert-ascending (deterministic).  A server with no routed load
        this wave keeps one aggregate-lane entry so the uniform cadence
        is unchanged.  ``VirtualClock.lane_overhead`` (default 0) is
        added per lane entry when a server's share splits."""
        alive = self._alive_mask()
        caps = getattr(self.pool, "capacities", None)
        lane_mode = self.ecfg.queue_mode == "expert"
        overhead = float(getattr(self.clk, "lane_overhead", 0.0))
        entries = []
        if self.ecfg.charge_imbalance:
            lanes = lane_loads(expert_load, self.pool.smap.table, S,
                               alive=alive, capacities=caps)
            total = float(lanes.sum())
            if total <= 0.0:
                return []
            scale = expert_dt * S / total
            for s in range(S):
                row = lanes[s]
                row_sum = float(row.sum())
                if row_sum <= 0.0:
                    continue
                if lane_mode:
                    nz = np.nonzero(row)[0]
                    extra = overhead if len(nz) > 1 else 0.0
                    for e in nz:
                        entries.append((s, int(e),
                                        scale * float(row[e]) + extra,
                                        float(row[e])))
                else:
                    entries.append((s, -1, scale * row_sum, row_sum))
            return entries
        n_alive = int(alive.sum())
        if n_alive <= 0:
            return []
        w_server = expert_dt * S / n_alive
        if not lane_mode:
            return [(s, -1, w_server, 1.0) for s in range(S) if alive[s]]
        lanes = lane_loads(expert_load, self.pool.smap.table, S,
                           alive=alive, capacities=caps)
        for s in range(S):
            if not alive[s]:
                continue
            row = lanes[s]
            row_sum = float(row.sum())
            if row_sum <= 0.0:
                # uniform cost model: a server with nothing routed this
                # wave still runs its uniform share (dispatch/combine
                # sync) — one aggregate-lane entry keeps the cadence
                entries.append((s, -1, w_server, 0.0))
                continue
            nz = np.nonzero(row)[0]
            extra = overhead if len(nz) > 1 else 0.0
            for e in nz:
                entries.append((s, int(e),
                                w_server * float(row[e]) / row_sum + extra,
                                float(row[e])))
        return entries

    # -------------------------------------------------------- async events
    def _handle_event(self, ev: Event) -> None:
        if ev.kind == "prefill_done":
            self._on_prefill_done(ev)
        elif ev.kind == "mb_done":
            self._on_mb_done(ev)
        elif ev.kind == "wave_done":
            for w in self._waves:
                if w["id"] == ev.payload["wave"]:
                    w["pending"].discard("wave")
                    break
            self._drain_finished_waves()

    def _on_prefill_done(self, ev: Event) -> None:
        p = ev.payload
        b, req = p["slot"], p["req"]
        if self.scheduler.slots[b] is not req:
            return                      # slot was aborted/released meanwhile
        self.scheduler.prefill_advanced(b, p["length"])
        if p["last"] and p["first"] is not None:
            req.output_tokens.append(p["first"])
            req.prefill_time = self.clock
            self.metrics.ttfts.append(self.clock - req.arrival_time)
            self.metrics.events.append(
                {"t": self.clock, "event": "prefill",
                 "rid": req.request_id,
                 "ttft": self.clock - req.arrival_time})

    def _on_mb_done(self, ev: Event) -> None:
        p = ev.payload
        if not self.tier.is_current(p["mb"], p["gen"]):
            return                      # re-dispatched or cancelled since
        mb = self.tier.mbs[p["mb"]]
        self.tier.mark_done(mb)
        self._mb_events.pop(mb.mb_id, None)
        # queueing delay: how long the micro-batch waited behind other
        # work in its lane/on its server — the first-class tail-latency
        # signal, attributed per (server, expert-lane) for the breakdown
        self.metrics.observe_queue_delay(mb.start_t - mb.enqueue_t,
                                         server=mb.server,
                                         expert=mb.expert)
        for w in self._waves:
            if w["id"] == mb.wave_id:
                w["pending"].discard(mb.mb_id)
                break
        self._drain_finished_waves()

    def _drain_finished_waves(self) -> None:
        """Retire drained waves in dispatch order.  Combine is in-order:
        a younger wave whose micro-batches all landed still waits for its
        elders, so per-slot token streams commit in sequence."""
        while self._waves and not self._waves[0]["pending"]:
            self._finish_wave(self._waves.popleft())

    def _finish_wave(self, w: dict) -> None:
        """The wave's last micro-batch drained (and every older wave
        retired): append the (already sampled) tokens at event time,
        retire finished requests."""
        sch = self.scheduler
        next_tokens = w["tokens"]
        produced = 0
        for b in w["slots"]:
            r = sch.slots[b]
            if r is None:
                continue
            tok = int(next_tokens[b])
            r.output_tokens.append(tok)
            r.token_times.append(self.clock)
            produced += 1
            self.metrics.total_output_tokens += 1
            done = (len(r.output_tokens) >= r.sampling.max_new_tokens or
                    (self.ecfg.eos_token is not None and
                     tok == self.ecfg.eos_token) or
                    len(r.prompt) + len(r.output_tokens) >=
                    self.ecfg.max_seq - 1)
            if done:
                # _slot_exhausted kept this slot out of every later wave,
                # so releasing here can't orphan an in-flight token
                r.finish_time = self.clock
                self.metrics.completed += 1
                self.metrics.itls.extend(r.itl())
                sch.release(b)
        self.metrics.timeline.append(
            {"t": self.clock, "tokens": produced, "halted": False})

    def _post_redispatch(self, mb: MicroBatch) -> None:
        """Post the fresh completion event for a failure-re-dispatched
        micro-batch (the cluster fans these to the owning client).  The
        superseded event for the old placement is cancelled outright —
        generation staleness remains as the second guard."""
        stale = self._mb_events.pop(mb.mb_id, None)
        if stale is not None:
            self.timeline.cancel(stale)
        self._mb_events[mb.mb_id] = self.timeline.post(
            mb.finish_t, "mb_done", mb=mb.mb_id, gen=mb.generation,
            wave=mb.wave_id, server=mb.server, expert=mb.expert)

    def _reconcile_waves(self) -> None:
        """Drop cancelled micro-batches from the in-flight waves (a
        failure with no survivors cancels outright) and cancel their
        pending completion events; retire waves left with nothing
        pending."""
        if self.tier is None:
            return
        for w in self._waves:
            for mb_id in list(w["pending"]):
                if mb_id == "wave":
                    continue
                mb = self.tier.mbs.get(mb_id)
                if mb is None or mb.cancelled:
                    w["pending"].discard(mb_id)
                    stale = self._mb_events.pop(mb_id, None)
                    if stale is not None:
                        self.timeline.cancel(stale)
        self._drain_finished_waves()

    def _drain_async(self) -> None:
        """Run the event timeline dry (the quiesce barrier before
        placement changes that re-shard the executor)."""
        if self.ecfg.exec_mode != "async":
            return
        while self.timeline.peek_time() is not None:
            ev = self.timeline.pop()
            self.clock = max(self.clock, ev.time)
            self._handle_event(ev)
        self.clock = max(self.clock, self._client_free_at)

    def run(self, max_steps: int = 10_000,
            on_step: Optional[Callable[["ServingEngine"], None]] = None
            ) -> ServingMetrics:
        """Drive until queue + slots drain (or max_steps)."""
        while (self.queue or any(s is not None for s in self.slots)) \
                and self.step_idx < max_steps:
            if on_step:
                on_step(self)
            self.step()
        self.metrics.wall_time = self.clock
        return self.metrics
