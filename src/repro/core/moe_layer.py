"""EaasMoELayer — the paper's contribution as a composable JAX module.

One function, three execution modes (DESIGN.md §2):

* ``axis_name=None``  — single-device simulation: the S logical servers are
  vmapped.  Used by CPU tests, the host-level serving engine and examples.
* ``mode="a2a"``      — SPMD inside shard_map: tokens sharded over the server
  axis; one all_to_all each way (train / prefill).
* ``mode="replicated"`` — SPMD decode: activations replicated over the server
  axis; zero request traffic, one psum to combine.

The full flow mirrors paper Fig. 4(b):

    router → mapping lookup (replica choice, liveness) → pack into
    per-server buffer slots → send → server: aggregate + grouped GEMM
    (group-shrink) + score-weight → return → combine (+ shared experts /
    dense residual on the client).
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.base import ModelConfig, MoEConfig
from repro.core import comm, dispatch, expert_server, mapping as emap, router
from repro.core.expert_server import ServerWeights


class MoERuntime(NamedTuple):
    """Runtime (non-compiled) state of the expert-service tier.

    Everything here is *data*: replacing these arrays re-routes traffic
    without touching the compiled program (failover / rebalance / scale).
    """

    mapping: jax.Array         # (E, R) int32 candidate server per replica
    alive: jax.Array           # (S,) bool server liveness
    local_table: jax.Array     # (S, E) int32 global eid -> server-local slot
    num_servers: int           # static: logical server count
    capacity: int              # static: tokens per (client, server) slot
    dispatch_method: str = "onehot"   # "onehot" | "sort"
    gemm_impl: str = "auto"
    # (E,) fp32 router-logit offset (traffic shaping — scenario set_skew);
    # None = unbiased.  Data like the mapping: rewriting it never recompiles.
    route_bias: Optional[jax.Array] = None
    # (S,) fp32 relative server capacities — replica picks spread tokens
    # proportionally to these (heterogeneous pools, paper §4.5 degree of
    # freedom 3); None = homogeneous, uniform spreading (bit-identical to
    # the pre-capacity behaviour).
    replica_weights: Optional[jax.Array] = None


class MoEStats(NamedTuple):
    aux_loss: jax.Array
    z_loss: jax.Array
    dropped: jax.Array         # tokens over slot capacity
    miss: jax.Array            # tokens sent to a server not hosting them
    expert_load: jax.Array     # (E,) token counts (feeds the load balancer)
    gemm_tiles_live: jax.Array  # grouped GEMM row tiles run, all servers
    gemm_tiles: jax.Array       # grouped GEMM row tiles laid out


def default_capacity(tokens_per_client: int, top_k: int, num_servers: int,
                     capacity_factor: float) -> int:
    """Paper §3.2 buffer sizing: fixed slots with a capacity-factor headroom."""
    ideal = tokens_per_client * top_k / num_servers
    return max(8, int(math.ceil(ideal * capacity_factor / 8.0) * 8))


# ----------------------------------------------------------------------- init

def init_eaas_moe(key, cfg: ModelConfig, num_servers: int,
                  n_redundant: int = 0,
                  redundant_table: Optional[np.ndarray] = None) -> Dict:
    """Router + per-server expert weights (+ shared / residual client FFNs)."""
    from repro.models.mlp import init_mlp

    m = cfg.moe
    assert m is not None
    ks = jax.random.split(key, 4)
    bank = expert_server.init_expert_weights(ks[0], cfg)
    if redundant_table is None:
        redundant_table = np.full((num_servers, max(n_redundant, 0)), -1,
                                  np.int32)
    server_w = expert_server.build_server_weights(
        bank, num_servers, redundant_table)
    params = {
        "router": router.init_router(ks[1], cfg.d_model, m.router_width,
                                     m.correction_bias),
        "servers": server_w,
    }
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    if m.num_shared_experts:
        params["shared"] = init_mlp(
            ks[2], cfg.d_model, m.d_expert * m.num_shared_experts,
            cfg.activation, dt)
    if m.dense_residual:
        params["residual"] = init_mlp(ks[3], cfg.d_model, cfg.d_ff,
                                      cfg.activation, dt)
    return params


def default_runtime(cfg: ModelConfig, num_servers: int,
                    tokens_per_client: int, max_replicas: int = 4,
                    gemm_impl: str = "auto",
                    redundant_table: Optional[np.ndarray] = None
                    ) -> MoERuntime:
    m = cfg.moe
    table = emap.default_mapping(m.num_experts, num_servers, max_replicas)
    if redundant_table is None:
        redundant_table = np.zeros((num_servers, 0), np.int32)
    local = expert_server.make_local_table(m.num_experts, num_servers,
                                           redundant_table)
    return MoERuntime(
        mapping=jnp.asarray(table),
        alive=jnp.ones((num_servers,), bool),
        local_table=jnp.asarray(local),
        num_servers=num_servers,
        capacity=default_capacity(tokens_per_client, m.top_k, num_servers,
                                  m.capacity_factor),
        gemm_impl=gemm_impl,
    )


# ---------------------------------------------------------------------- apply

def _client_extras(params: Dict, x: jax.Array, cfg_moe: MoEConfig,
                   activation: str) -> jax.Array:
    """Shared experts + dense residual — the client-side dense FFN tier,
    traced in the ``combine`` stage."""
    from repro.models.mlp import mlp

    with tracing.stage("combine"):
        extra = jnp.zeros_like(x)
        if "shared" in params:
            extra = extra + mlp(params["shared"], x, activation)
        if "residual" in params:
            extra = extra + mlp(params["residual"], x, activation)
        return extra


def _share(expert_ids: jax.Array, cfg_moe: MoEConfig):
    """(ids among the experts held, which routes are held) of global
    routes (T, k); the mask is None when the layer holds every expert."""
    if cfg_moe.holds_all:
        return expert_ids, None
    local = expert_ids - cfg_moe.first_expert
    held = (local >= 0) & (local < cfg_moe.num_experts)
    return jnp.clip(local, 0, cfg_moe.num_experts - 1), held


def eaas_moe_apply(params: Dict, x: jax.Array, cfg_moe: MoEConfig,
                   runtime: MoERuntime, *, activation: str = "swiglu",
                   axis_name: Optional[str] = None, mode: str = "local",
                   token_salt: Optional[jax.Array] = None,
                   ) -> Tuple[jax.Array, MoEStats]:
    """Apply the EAAS MoE layer to x: (T, d) -> (T, d).

    A layer that holds a share of the routed experts (``cfg_moe``'s
    ``first_expert`` .. + ``num_experts`` of ``router_width``) routes over
    all of them, sends only the routes to its own experts, and returns
    their part of the result (plus the client's dense extras); its expert
    load counts the held routes, by held expert.

    In SPMD modes this must be called inside shard_map with ``axis_name``
    bound to the server mesh axis; params["servers"] arrays then hold only
    the local shard (leading dim 1) and are squeezed here.

    Device work is tagged by stage (:func:`repro.tracing.stage`):
    ``router``, ``dispatch`` (replica lookup, packing, the send),
    ``server`` (the expert servers; their grouped GEMM kernel tags itself
    ``gemm``) and ``combine`` (the return, the weighted sum and the client's
    dense extras).
    """
    T, d = x.shape
    S, C = runtime.num_servers, runtime.capacity

    # ---- client: route + resolve service instances ----------------------
    with tracing.stage("router"):
        r = router.route(params["router"], x, cfg_moe,
                         bias=runtime.route_bias)
        expert_ids, held = _share(r.expert_ids, cfg_moe)
        expert_load = router.expert_load(
            expert_ids if held is None
            else jnp.where(held, expert_ids, cfg_moe.num_experts),
            cfg_moe.num_experts)
    with tracing.stage("dispatch"):
        if token_salt is None:
            token_salt = jnp.arange(T, dtype=jnp.int32)[:, None] + jnp.arange(
                expert_ids.shape[1], dtype=jnp.int32)[None, :]
        server_ids = emap.lookup(runtime.mapping, runtime.alive,
                                 expert_ids, token_salt,
                                 weights=runtime.replica_weights)

        # ---- client: pack buffer slots (paper §3.2) ----------------------
        buffers = dispatch.pack(x, expert_ids, r.scores, server_ids, S, C,
                                method=runtime.dispatch_method, held=held)
        hid, eid, sc, cnt = comm.send_to_servers(buffers, axis_name, mode)

    # ---- server compute ----------------------------------------------------
    sw = params["servers"]
    with tracing.stage("server"):
        if axis_name is None:
            # vmap the stateless server over the S logical instances
            def one_server(wg, wu, wd, tbl, hid, eid, sc, cnt):
                w = ServerWeights(wg, wu, wd, tbl)
                out, st = expert_server.serve(hid[None], eid[None], sc[None],
                                              cnt[None], w,
                                              impl=runtime.gemm_impl)
                return out[0], st
            out_slots, st = jax.vmap(one_server)(
                sw["w_gate"], sw["w_up"], sw["w_down"], runtime.local_table,
                hid, eid, sc, cnt)
            st = jax.tree.map(jnp.sum, st)
        else:
            w = ServerWeights(sw["w_gate"][0], sw["w_up"][0],
                              sw["w_down"][0], runtime.local_table[0])
            out_slots, st = expert_server.serve(hid, eid, sc, cnt, w,
                                                impl=runtime.gemm_impl)

    # ---- client: combine (weighted sum arrives pre-weighted) -------------
    with tracing.stage("combine"):
        result = comm.return_to_clients(out_slots, axis_name, mode)
        y = dispatch.combine(result, buffers.combine_slot, out_dtype=x.dtype)
        y = comm.finalize_combine(y, axis_name, mode)
        y = y + _client_extras(params, x, cfg_moe, activation)

    stats = MoEStats(
        aux_loss=r.aux_loss,
        z_loss=r.z_loss,
        dropped=buffers.dropped,
        miss=st.miss,
        expert_load=expert_load,
        gemm_tiles_live=st.tiles_live,
        gemm_tiles=st.tiles,
    )
    return y, stats
