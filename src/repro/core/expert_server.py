"""The stateless expert server (paper §3.3, Fig. 5).

A server aggregates every ready client slot into one dynamic batch,
reorganizes tokens by (local) expert, runs grouped GEMM over the active
groups only (group-shrink), weights by the router scores carried in the
payload, and writes the results back into the same slot layout.

The server is a *pure function* — it holds no sequence state and initiates
no communication (comm.py is invoked by the client side only).  That purity
is the paper's statelessness argument, and it is what makes replication,
failover and elastic scaling trivial.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models.common import dense_init


class ServerWeights(NamedTuple):
    """The expert weights of S servers: primaries + redundant (replica) slots.

    Shapes: w_gate/w_up: (S, L, d, f)   w_down: (S, L, f, d)
    where L = E/S primaries + n_red redundant slots; a stacked layer axis
    may lead them, (layers, S, L, ...), when :func:`serve` is given the
    layer to read.
    ``local_table``: (S, E) int32 — global expert id -> local slot (or -1).
    """

    w_gate: jax.Array
    w_up: jax.Array
    w_down: jax.Array
    local_table: jax.Array


def init_expert_weights(key, cfg: ModelConfig) -> Dict:
    """Global expert bank: (E, d, f) — sharded over the server axis at launch."""
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_expert, m.num_experts
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    ks = jax.random.split(key, 3)
    return {
        "w_gate": jax.vmap(lambda k: dense_init(k, d, f, dt))(
            jax.random.split(ks[0], E)),
        "w_up": jax.vmap(lambda k: dense_init(k, d, f, dt))(
            jax.random.split(ks[1], E)),
        "w_down": jax.vmap(lambda k: dense_init(k, f, d, dt))(
            jax.random.split(ks[2], E)),
    }


def _layout_ids(num_experts: int, num_servers: int,
                redundant_table: np.ndarray) -> np.ndarray:
    """(S, L) local-slot → global-expert-id layout (-1 = empty slot).

    Slots 0..E/S-1 are the block-contiguous primaries; the rest mirror
    ``redundant_table``.
    """
    E, S = num_experts, num_servers
    per = E // S
    assert per * S == E, (E, S)
    primary_ids = np.arange(E, dtype=np.int32).reshape(S, per)
    red = np.asarray(redundant_table, np.int32)              # (S, n_red)
    return np.concatenate([primary_ids, red], axis=1)        # (S, L)


def make_local_table(num_experts: int, num_servers: int,
                     redundant_table: np.ndarray) -> np.ndarray:
    """(S, E) global-expert-id → local-slot lookup (-1 = not hosted).

    Slots 0..E/S-1 are the block-contiguous primaries; the rest mirror
    ``redundant_table``.  This is *placement data* (runtime, not params):
    rebalancing rewrites it without touching the compiled program.
    """
    E, S = num_experts, num_servers
    local_ids = _layout_ids(E, S, redundant_table)
    local_table = np.full((S, E), -1, np.int32)
    for s in range(S):
        for slot, e in enumerate(local_ids[s]):
            if e >= 0 and local_table[s, e] < 0:
                local_table[s, e] = slot
    return local_table


def build_server_weights(bank: Dict, num_servers: int,
                         redundant_table: np.ndarray) -> Dict:
    """Materialize per-server weight arrays from the global bank.

    Returns stacked per-server arrays (S, L, ...) (shard dim0 over the server
    axis at launch).  Redundant slots are *copies* — replication costs
    memory, exactly as in the paper.
    """
    E = bank["w_gate"].shape[0]
    S = num_servers
    per = E // S
    assert per * S == E

    primary_ids = np.arange(E, dtype=np.int32).reshape(S, per)
    red = np.asarray(redundant_table, np.int32)              # (S, n_red)
    local_ids = np.concatenate([primary_ids, red], axis=1)   # (S, L)

    gather_ids = jnp.asarray(np.maximum(local_ids, 0))       # (S, L)
    mask = jnp.asarray(local_ids >= 0)[..., None, None]

    def per_server(w):
        return jnp.where(mask, w[gather_ids], 0)

    return {
        "w_gate": per_server(bank["w_gate"]),                # (S, L, d, f)
        "w_up": per_server(bank["w_up"]),
        "w_down": per_server(bank["w_down"]),
    }


def extract_bank(server_w: Dict, num_experts: int) -> Dict:
    """Recover the global (…, E, d, f) expert bank from per-server arrays.

    Inverse of :func:`build_server_weights` restricted to the primary slots
    (which are block-contiguous and never move — redundant slots are mere
    copies).  Accepts arbitrary leading dims (e.g. a scan-stacked layer
    axis): (…, S, L, d, f) → (…, E, d, f).
    """
    def un_shard(w):
        *lead, S, L, a, b = w.shape
        per = num_experts // S
        assert per * S == num_experts, (num_experts, S)
        return w[..., :per, :, :].reshape(*lead, num_experts, a, b)

    return {k: un_shard(v) for k, v in server_w.items()}


def redundant_slot(num_experts: int, num_servers: int, j: int) -> int:
    """Local slot index of redundant column ``j`` — slots 0..E/S-1 are the
    block-contiguous primaries (single owner of the layout knowledge in
    :func:`_layout_ids`; the rebalance paths build their weight-copy
    targets through this)."""
    return num_experts // num_servers + j


def replica_columns(redundant_table: np.ndarray,
                    expert: int) -> Tuple[Tuple[int, int], ...]:
    """``(server, column)`` positions of every replica slot holding
    ``expert`` in the redundant table, in deterministic row-major order —
    the scale-to-zero page-out scan (each hit becomes a
    ``(server, redundant_slot(...), -1)`` eviction update for
    :func:`migrate_slots`)."""
    red = np.asarray(redundant_table)
    return tuple((int(s), int(j)) for s, j in np.argwhere(red == expert))


def migrate_slots(server_w: Dict, num_experts: int,
                  updates) -> Dict:
    """Copy expert weights into specific server slots in place — the weight
    half of one incremental rebalance chunk (paper §4.5 live migration).

    updates: ``[(server, local_slot, expert_id)]``; ``expert_id == -1``
    zeroes the slot (replica dropped).  Sources are read straight from the
    block-contiguous primary slots (expert ``e`` lives at server ``e//per``
    slot ``e%per``), which never move and are disjoint from the redundant
    targets — so a chunk is O(chunk) data movement, not a bank rebuild,
    and chunks compose in any order.  Accepts arbitrary leading dims
    (scan-stacked layer axis), like the other weight-path helpers.
    """
    def apply(w):
        S = w.shape[-4]
        per = num_experts // S
        assert per * S == num_experts, (num_experts, S)
        for s, slot, e in updates:
            src = w[..., e // per, e % per, :, :] if e >= 0 else 0
            w = w.at[..., s, slot, :, :].set(src)
        return w

    return {k: apply(v) for k, v in server_w.items()}


def reshard_server_weights(server_w: Dict, num_experts: int,
                           new_servers: int,
                           redundant_table: np.ndarray) -> Dict:
    """Re-materialize per-server weights for a different pool size.

    This is elastic scaling's weight path (paper §5.3): the global bank is
    recovered from the primary slots and re-laid-out for ``new_servers``
    with the new replication plan.  Pure data movement — router / client
    params are untouched, expert math is bit-identical.
    """
    bank = extract_bank(server_w, num_experts)
    local_ids = _layout_ids(num_experts, new_servers, redundant_table)
    gather = jnp.asarray(np.maximum(local_ids, 0).reshape(-1))   # (S'*L',)
    mask = jnp.asarray(local_ids >= 0)[..., None, None]          # (S', L',1,1)

    def re_shard(w):
        *lead, E, a, b = w.shape
        g = jnp.take(w, gather, axis=-3)
        g = g.reshape(*lead, *local_ids.shape, a, b)
        return jnp.where(mask, g, 0)

    return {k: re_shard(v) for k, v in bank.items()}


class ServeStats(NamedTuple):
    miss: jax.Array           # tokens whose expert this server doesn't host
    served: jax.Array         # valid tokens processed
    tiles_live: jax.Array     # grouped GEMM row tiles run (Pallas only)
    tiles: jax.Array          # grouped GEMM row tiles laid out (Pallas only)


def serve(tokens: jax.Array, expert_ids: jax.Array, scores: jax.Array,
          counts: jax.Array, weights: ServerWeights, *,
          impl: str = "auto", layer: Optional[jax.Array] = None
          ) -> Tuple[jax.Array, ServeStats]:
    """Process the aggregated dynamic batches of S servers in one call.

    tokens: (S, Clients, C, d) — each server's view of every client slot;
    expert_ids/scores: (S, Clients, C); counts: (S, Clients) headers.
    Returns (S, Clients, C, d) score-weighted outputs (zeros on invalid
    slots) and ServeStats summed over the servers.

    The servers share one grouped GEMM per weight: a row's group is
    ``server·L + slot``, so every server's experts are groups of one
    (S·L, d, f) bank, a reshape of the stacked weights.  Given ``layer``,
    the weights carry a leading layer axis and the GEMM reads that layer's
    groups in place, from ``layer·S·L`` on: no weight is sliced or copied.
    """
    S, Sc, C, d = tokens.shape
    M = Sc * C
    N = S * M
    x = tokens.reshape(N, d)
    eid = expert_ids.reshape(S, M)
    sc = scores.reshape(N)
    valid = (jnp.arange(C)[None, None, :] < counts[:, :, None]).reshape(S, M)
    valid &= eid >= 0

    L = weights.w_gate.shape[-3]
    G = S * L                          # groups; group G is the pad group
    # each row's slot, from its own server's table
    slot = jnp.take_along_axis(weights.local_table, jnp.clip(eid, 0), axis=1)
    group = (jnp.arange(S, dtype=slot.dtype)[:, None] * L + slot).reshape(N)
    valid = valid.reshape(N)
    hosted = slot.reshape(N) >= 0
    live = valid & hosted
    group = jnp.where(live, group, G)

    # ---- reorganize tokens by (server, local expert) (paper Fig. 5) ----
    order = jnp.argsort(group)                                # stable
    xs = x[order]
    group_sizes = jnp.bincount(group, length=G + 1)[:G]       # drop pad group

    # ---- grouped GEMM over active groups only (group-shrink) -----------
    # per-expert capacity for the dense lowering: ideal share × the buffer
    # capacity factor (under-provisioned experts drop, exactly like slots)
    ecap = max(8, ((-(-(N * 5) // (4 * G))) + 7) // 8 * 8)
    offset = 0 if layer is None else layer * G

    def gg(a, w):
        return kops.grouped_gemm_counted(
            a, w.reshape(-1, *w.shape[-2:]), group_sizes, impl=impl,
            expert_capacity=ecap, group_offset=offset)

    h_gate, t_gate = gg(xs, weights.w_gate)
    h_up, t_up = gg(xs, weights.w_up)
    h = jax.nn.silu(h_gate.astype(jnp.float32)).astype(h_up.dtype) * h_up
    y, t_down = gg(h, weights.w_down)

    # ---- score weighting + masking, back to slot order ------------------
    y = y.astype(jnp.float32) * sc[order][:, None]
    y = jnp.where(live[order][:, None], y, 0)                 # pad rows off
    out = jnp.zeros((N, d), jnp.float32).at[order].set(y)
    out = out.reshape(S, Sc, C, d).astype(tokens.dtype)
    return out, ServeStats(
        miss=jnp.sum(valid & ~hosted), served=jnp.sum(live),
        tiles_live=t_gate.live + t_up.live + t_down.live,
        tiles=t_gate.total + t_up.total + t_down.total)
