"""Elastic scaling of the expert-service tier (paper §5.3).

Monolithic EP scales in units of whole communication groups; EAAS scales one
server at a time.  On TPU the *logical* server pool (mapping table) changes
freely at runtime; the *physical* mesh changes through AOT-compiled variants
(jit caches one executable per server-count).  This module provides:

* :class:`ServerPool` — host-side pool with add/remove/rebalance, emitting
  fresh MoERuntime arrays each change (no recompile for liveness/mapping
  changes; recompile only when the physical mesh itself grows).
* :func:`provision` — the traffic→server-count policy used by the weak-
  scaling benchmark (the paper's 37.5% saving comes from this curve).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import load_balance
from repro.core.mapping import ExpertServerMap
from repro.core.moe_layer import MoERuntime, default_capacity


@dataclass
class ServerPool:
    """Logical expert-server pool with liveness + replication state."""

    cfg: ModelConfig
    num_servers: int
    tokens_per_client: int
    n_redundant: int = 2
    max_replicas: int = 4
    # relative per-server capacity weights ((S,) or None = homogeneous);
    # heterogeneous pools tilt replica placement toward the big servers
    capacities: np.ndarray = None
    stats: load_balance.ExpertStats = None
    smap: ExpertServerMap = None
    redundant_table: np.ndarray = None
    route_bias: np.ndarray = None

    def __post_init__(self):
        E = self.cfg.moe.num_experts          # the experts held
        self.stats = load_balance.ExpertStats(E)
        # router-logit offsets span every routed expert, held or not
        self.route_bias = np.zeros(self.cfg.moe.router_width, np.float32)
        # scale-to-zero state: experts paged out of the tier (replica slots
        # evicted; the primary shard stays addressable as the page-in
        # source) and, for hysteresis, when each one last paged back in
        self.cold: set = set()
        self.page_in_t: dict = {}
        mapping, red = self.plan(np.ones(E))
        self.smap = self._make_smap(mapping)
        self.redundant_table = red

    def _make_smap(self, mapping: np.ndarray) -> ExpertServerMap:
        """Build the live mapping table with replica-column headroom: an
        in-flight incremental migration registers a new replica before a
        later chunk drops the old one, so an expert can transiently hold up
        to (old + new) replicas — double width absorbs the worst case."""
        E = mapping.shape[0]
        pad = np.full((E, self.max_replicas), -1, np.int32)
        return ExpertServerMap(np.concatenate([mapping, pad], axis=1),
                               self.num_servers)

    # ------------------------------------------------------------- events
    def server_failed(self, rank: int) -> None:
        self.smap.mark_dead(rank)

    def server_recovered(self, rank: int) -> None:
        self.smap.mark_alive(rank)

    def observe_load(self, expert_load: np.ndarray) -> None:
        self.stats.update(expert_load)

    def set_route_bias(self, bias: np.ndarray) -> None:
        """Install a router-logit offset (scenario traffic shaping)."""
        bias = np.asarray(bias, np.float32)
        assert bias.shape == self.route_bias.shape, bias.shape
        self.route_bias = bias

    # ---------------------------------------------------------- balancing
    def plan(self, load: Optional[np.ndarray] = None
             ) -> Tuple[np.ndarray, np.ndarray]:
        """EPLB plan for this pool from ``load`` (default: the traffic EMA,
        uniform when nothing has been observed)."""
        if load is None:
            load = (self.stats.ema if self.stats.ema is not None
                    else np.ones(self.cfg.moe.num_experts))
        if self.cold:
            # paged-out experts must not attract replicas: mask their load
            # so the planner spends the redundant slots on resident experts
            load = np.asarray(load, np.float64).copy()
            for e in self.cold:
                if 0 <= e < load.shape[0]:
                    load[e] = 0.0
        return load_balance.eplb_plan(
            load, self.num_servers, self.n_redundant, self.max_replicas,
            capacities=self.capacities)

    @property
    def plan_digest(self) -> str:
        """Digest of the live placement's replica sets (order-free)."""
        return load_balance.plan_digest(self.smap.table, self.num_servers)

    def current_imbalance(self) -> float:
        """max/mean per-alive-server load of the traffic EMA under the live
        placement — the factor the slowest server stretches a decode step."""
        if self.stats.ema is None:
            return 1.0
        return load_balance.imbalance(
            self.stats.ema, self.smap.table, self.num_servers,
            alive=self.smap.alive, capacities=self.capacities)

    def client_view(self, client_id: int = 0) -> "PoolClient":
        """A per-client handle on this shared pool (cluster front-end)."""
        return PoolClient(self, client_id)

    def apply_plan(self, mapping: np.ndarray, red: np.ndarray) -> None:
        """Adopt a placement wholesale, preserving liveness (the one-shot
        path; the rebalance controller instead converges incrementally via
        drop_replica/register_replica + per-chunk weight migration)."""
        alive = self.smap.alive.copy()
        self.smap = self._make_smap(mapping)
        self.smap.alive = alive
        self.redundant_table = red

    def rebalance(self) -> bool:
        """Re-plan replication from traffic EMA (paper §4.5 / EPLB).

        Skips the runtime rebuild when the new plan is placement-identical
        to the live table (same replica sets — column order is routing-
        invisible); returns whether the placement changed.
        """
        if self.stats.ema is None:
            return False
        mapping, red = self.plan()
        if load_balance.plan_digest(mapping,
                                    self.num_servers) == self.plan_digest:
            return False
        self.apply_plan(mapping, red)
        return True

    # ------------------------------------------------------- scale-to-zero
    def page_out_experts(self, experts
                         ) -> Tuple[List[int], List[Tuple[int, int, int]]]:
        """Page cold experts out of the tier (serverless experts à la
        MoEless): every replica slot is evicted — dropped from the live
        mapping table and zeroed in the redundant weight banks — and the
        expert is marked cold.  The primary shard stays addressable as the
        page-in source, so a token that *does* route to a cold expert still
        computes exactly (the elasticity identity contract); it pays the
        modeled cold-start penalty instead of dropping.

        Returns ``(paged, updates)``: the experts actually paged and the
        ``(server, local_slot, -1)`` weight updates the caller must apply
        through its migration path (``apply_migration`` /
        ``expert_server.migrate_slots``) to physically zero the bank slots.
        """
        from repro.core import expert_server
        E = self.cfg.moe.num_experts
        prim = load_balance.primary_owner(E, self.num_servers)
        paged: List[int] = []
        updates: List[Tuple[int, int, int]] = []
        for e in sorted({int(x) for x in experts}):
            if not 0 <= e < E or e in self.cold:
                continue
            for s, j in expert_server.replica_columns(
                    self.redundant_table, e):
                self.redundant_table[s, j] = -1
                updates.append((s, expert_server.redundant_slot(
                    E, self.num_servers, j), -1))
            row = self.smap.table[e]
            row[:] = -1
            row[0] = prim[e]           # primary only: the page-in source
            self.cold.add(e)
            self.page_in_t.pop(e, None)
            paged.append(e)
        return paged, updates

    def page_in_expert(self, e: int, t: float) -> bool:
        """First token routed to a cold expert: mark it resident again and
        record the page-in time (the autoscaler's hysteresis protects a
        freshly paged-in expert from immediately paging back out).  The
        expert serves from its primary shard until the next rebalance
        re-plans replicas for it — ``plan`` stops masking its load the
        moment it leaves ``cold``.  Returns whether a page-in happened."""
        e = int(e)
        if e not in self.cold:
            return False
        self.cold.discard(e)
        self.page_in_t[e] = float(t)
        return True

    def resident_fraction(self) -> float:
        """Share of experts currently resident in the tier (1.0 = nothing
        paged out) — the expert-tier term of provisioned-resource
        accounting."""
        return 1.0 - len(self.cold) / self.cfg.moe.num_experts

    # ------------------------------------------------------------- elastic
    def feasible_counts(self) -> List[int]:
        """Pool sizes the block-contiguous primary layout supports (E % n == 0)."""
        E = self.cfg.moe.num_experts
        return [n for n in range(1, E + 1) if E % n == 0]

    def scale_to(self, n: int) -> None:
        """Grow/shrink the logical pool to ``n`` servers (paper §5.3).

        Re-plans the EPLB mapping for the new size from the traffic EMA
        (uniform load when no traffic has been observed yet) and preserves
        the liveness mask of surviving ranks; newly added ranks start
        alive.  The caller owns the weight path — see
        :func:`repro.core.expert_server.reshard_server_weights`.
        """
        E = self.cfg.moe.num_experts
        if E % n:
            raise ValueError(
                f"cannot scale to {n} servers: {E} experts need E % n == 0 "
                f"(feasible: {self.feasible_counts()})")
        if n == self.num_servers:
            return
        load = self.stats.ema if self.stats.ema is not None else np.ones(E)
        old_alive = self.smap.alive
        self.num_servers = n
        if self.capacities is not None:     # keep surviving ranks' weights
            caps = np.ones(n, np.float64)
            k = min(len(self.capacities), n)
            caps[:k] = np.asarray(self.capacities, np.float64)[:k]
            self.capacities = caps
        # a resize re-provisions every rank (weights reshard from the master
        # bank), so paged-out experts come back resident; the autoscaler
        # pages them out again once its cooldown re-opens
        self.cold.clear()
        self.page_in_t.clear()
        mapping, red = self.plan(load)
        self.smap = self._make_smap(mapping)
        k = min(len(old_alive), n)
        self.smap.alive[:k] = old_alive[:k]
        self.redundant_table = red

    # ------------------------------------------------------------ runtime
    def runtime(self, gemm_impl: str = "auto") -> MoERuntime:
        from repro.core import expert_server
        table, alive = self.smap.device_arrays()
        m = self.cfg.moe
        local = expert_server.make_local_table(
            m.num_experts, self.num_servers, self.redundant_table)
        return MoERuntime(
            mapping=table,
            alive=alive,
            local_table=jnp.asarray(local),
            num_servers=self.num_servers,
            capacity=default_capacity(self.tokens_per_client, m.top_k,
                                      self.num_servers, m.capacity_factor),
            gemm_impl=gemm_impl,
            route_bias=jnp.asarray(self.route_bias),
            replica_weights=(None if self.capacities is None
                             else jnp.asarray(self.capacities, jnp.float32)),
        )


class PoolClient:
    """One attention client's handle on a *shared* :class:`ServerPool`.

    The paper's clients each keep a local expert-to-server mapping *mask*
    over the shared service-discovery table: the table itself (placement,
    replicas, global liveness) is one object every client reads — so
    expert-replica failures and migrations are observed consistently — while
    a client may additionally mask out servers *it* has locally observed
    misbehaving (e.g. a request timeout) before the monitor confirms the
    failure pool-wide.  Everything except :meth:`runtime` delegates to the
    underlying pool; ``runtime`` ANDs the client mask into the liveness
    array fed to the jitted step (pure data — never recompiles).
    """

    def __init__(self, pool: ServerPool, client_id: int = 0):
        self.pool = pool
        self.client_id = client_id
        self._masked: set = set()      # server ranks this client masked out

    # ------------------------------------------------------- client mask
    def mask_server(self, rank: int) -> None:
        """Locally stop routing to ``rank`` (this client only)."""
        self._masked.add(int(rank))

    def unmask_server(self, rank: int) -> None:
        self._masked.discard(int(rank))

    @property
    def masked_servers(self) -> Tuple[int, ...]:
        return tuple(sorted(self._masked))

    def alive_mask(self) -> np.ndarray:
        """(S,) shared liveness AND the client's local mask."""
        mask = self.pool.smap.alive.copy()
        for r in self._masked:
            if r < mask.shape[0]:
                mask[r] = False
        return mask

    def runtime(self, gemm_impl: str = "auto") -> MoERuntime:
        rt = self.pool.runtime(gemm_impl)
        if not self._masked:
            return rt                  # fast path: the shared view verbatim
        return rt._replace(alive=jnp.asarray(self.alive_mask()))

    # ------------------------------------------------------- delegation
    def __getattr__(self, name):
        return getattr(self.pool, name)


def provision(request_rate: float, rate_per_server: float,
              granularity: int = 1) -> int:
    """Servers needed for a traffic level, at EAAS (1) vs monolithic (group)
    granularity.  The scaling benchmark sweeps this for both."""
    need = max(1, math.ceil(request_rate / max(rate_per_server, 1e-9)))
    return int(math.ceil(need / granularity) * granularity)


def resource_saving(request_rate: float, rate_per_server: float,
                    monolithic_group: int) -> float:
    """Fraction of chips EAAS saves vs group-granular scaling (paper: 37.5%)."""
    fine = provision(request_rate, rate_per_server, 1)
    coarse = provision(request_rate, rate_per_server, monolithic_group)
    return 1.0 - fine / coarse
