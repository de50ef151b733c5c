"""Architecture stack builder: one entrypoint for all 10 assigned archs.

``build_model(cfg)`` returns a :class:`Model` bundle:

* ``init_params(key)``                       — parameter pytree
* ``loss_fn(params, batch, ctx)``            — train forward (+ MoE aux)
* ``prefill(params, tokens, ctx, ...)``      — fill caches, last logits
* ``decode_step(params, token, cache, ctx)`` — one-token serve step

Layer stacks use ``lax.scan`` over stacked per-layer params (homogeneous
groups); heterogeneous archs scan their repeating unit (gemma3 5:1 groups,
zamba2 mamba×6+shared-attn groups, kimi dense-prefix + MoE scan).

Distribution: dense math runs under GSPMD steered by sharding constraints
(:mod:`repro.distributed.sharding_rules`); the EAAS MoE layer is an explicit
``shard_map`` island (:func:`repro.core.moe_layer.eaas_moe_apply`); long-
context decode uses the explicit sequence-parallel attention island.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.base import ModelConfig
from repro.core import moe_layer as eaas
from repro.core.moe_layer import MoERuntime, MoEStats
from repro.models import attention as attn
from repro.models import kv_cache as kvc
from repro.models import mamba as mam
from repro.models import rwkv as rwk
from repro.models.common import embed_init, rms_norm, rms_norm_init
from repro.models.mlp import init_mlp, mlp
from repro.models.rope import text_mrope_positions


# ---------------------------------------------------------------------------
# Parallel context
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ParallelCtx:
    """How a step is distributed.  ``mesh=None`` = single-device (tests)."""

    mesh: Any = None
    axis_data: Tuple[str, ...] = ("data",)      # batch axes (may incl. "pod")
    axis_model: str = "model"
    moe_runtime: Optional[MoERuntime] = None
    moe_mode: str = "local"                     # local | a2a | replicated
    gemm_impl: str = "auto"
    seq_shard_cache: bool = False               # SP decode (slot-sharded)
    seq_shard_axes: Tuple[str, ...] = ()        # slot axes (default: data)
    # train-only: shard the residual stream over model between blocks
    # (Megatron-SP): remat-saved carries shrink model_size×; prefill skips
    # it (no backward ⇒ no saved carries, the reshards would be pure cost)
    sp_residual: bool = False
    remat: bool = True
    ce_chunk: int = 512
    dbo: bool = False                           # double-batch-overlap split
    # fully unroll layer/CE scans (dry-run cost probes need bodies counted
    # per trip; XLA cost_analysis counts while-loop bodies once)
    unroll_scans: bool = False

    @property
    def dp_axes(self) -> Tuple[str, ...]:
        return self.axis_data

    def constraint(self, x, spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))


def _P(*args):
    return jax.sharding.PartitionSpec(*args)


# ---------------------------------------------------------------------------
# MoE layer wrapper (local vs shard_map island)
# ---------------------------------------------------------------------------

def _moe_apply(params_moe: Dict, x2d: jax.Array, cfg: ModelConfig,
               ctx: ParallelCtx) -> Tuple[jax.Array, MoEStats]:
    m = cfg.moe
    rt = ctx.moe_runtime
    assert rt is not None, "MoE arch needs ctx.moe_runtime"

    if ctx.mesh is None:
        return eaas.eaas_moe_apply(params_moe, x2d, m, rt,
                                   activation=cfg.activation,
                                   axis_name=None, mode="local")

    mode = ctx.moe_mode
    dp = ctx.dp_axes
    model_ax = ctx.axis_model
    tok_spec = (_P((*dp, model_ax), None) if mode == "a2a"
                else _P((*dp,), None))

    routed = {k: params_moe[k] for k in ("router", "servers")}
    in_specs = (
        {"router": jax.tree.map(lambda _: _P(), params_moe["router"]),
         "servers": {"w_gate": _P(model_ax, None, None, None),
                     "w_up": _P(model_ax, None, None, None),
                     "w_down": _P(model_ax, None, None, None)}},
        tok_spec,
        MoERuntime(mapping=_P(None, None), alive=_P(None),
                   local_table=_P(model_ax, None),
                   num_servers=None, capacity=None, dispatch_method=None,
                   gemm_impl=None),
    )
    n_shards = int(np.prod([ctx.mesh.shape[a] for a in dp])) * (
        ctx.mesh.shape[model_ax] if mode == "a2a" else 1)
    all_axes = (*dp, model_ax)

    def island(p, x, rt_arrays):
        rt_local = rt._replace(mapping=rt_arrays.mapping,
                               alive=rt_arrays.alive,
                               local_table=rt_arrays.local_table)
        y, st = eaas.eaas_moe_apply(
            p, x, m, rt_local, activation=cfg.activation,
            axis_name=model_ax, mode=mode)
        # global stats (replicated out): sum over participating shards
        def allsum(v):
            return jax.lax.psum(v, all_axes)
        denom = n_shards if mode == "a2a" else n_shards * ctx.mesh.shape[model_ax]
        st = MoEStats(
            aux_loss=allsum(st.aux_loss) / denom,
            z_loss=allsum(st.z_loss) / denom,
            dropped=allsum(st.dropped) // (
                1 if mode == "a2a" else ctx.mesh.shape[model_ax]),
            miss=allsum(st.miss),
            expert_load=allsum(st.expert_load) // (
                1 if mode == "a2a" else ctx.mesh.shape[model_ax]),
            # every shard runs its own server's kernel
            gemm_tiles_live=allsum(st.gemm_tiles_live),
            gemm_tiles=allsum(st.gemm_tiles),
        )
        return y, st

    rt_arrays = MoERuntime(mapping=rt.mapping, alive=rt.alive,
                           local_table=rt.local_table,
                           num_servers=None, capacity=None,
                           dispatch_method=None, gemm_impl=None)
    stats_specs = MoEStats(aux_loss=_P(), z_loss=_P(), dropped=_P(),
                           miss=_P(), expert_load=_P(),
                           gemm_tiles_live=_P(), gemm_tiles=_P())
    fn = jax.shard_map(island, mesh=ctx.mesh,
                       in_specs=in_specs,
                       out_specs=(tok_spec, stats_specs),
                       check_vma=False)
    y, st = fn(routed, x2d, rt_arrays)

    # client-side dense extras (shared experts / dense residual) run in
    # GSPMD land with TP sharding like any dense FFN
    extra = eaas._client_extras(
        {k: v for k, v in params_moe.items() if k in ("shared", "residual")},
        x2d, m, cfg.activation)
    return y + extra, st


# ---------------------------------------------------------------------------
# Transformer block (dense or MoE FFN)
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, is_moe: bool, num_servers: int,
                redundant_table) -> Dict:
    ks = jax.random.split(key, 4)
    p = {
        "ln1": rms_norm_init(cfg.d_model),
        "ln2": rms_norm_init(cfg.d_model),
        "attn": attn.init_attention(ks[0], cfg),
    }
    if is_moe:
        p["moe"] = eaas.init_eaas_moe(
            ks[1], cfg, num_servers,
            redundant_table=redundant_table)
    else:
        p["mlp"] = init_mlp_for_cfg(ks[1], cfg)
    return p


def init_mlp_for_cfg(key, cfg: ModelConfig) -> Dict:
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return init_mlp(key, cfg.d_model, cfg.d_ff, cfg.activation, dt)


def _block_train(p: Dict, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array, ctx: ParallelCtx, *,
                 is_local: bool = False, mrope_positions=None
                 ) -> Tuple[jax.Array, Optional[MoEStats]]:
    """Full-sequence block (train / prefill shares math, no cache)."""
    B, S, d = x.shape
    h = rms_norm(x, p["ln1"], cfg.rms_norm_eps)
    h = attn.full_attention(p["attn"], cfg, h, positions, is_local=is_local,
                            mrope_positions=mrope_positions,
                            unroll=ctx.unroll_scans)
    x = x + h
    res_spec = (_P(ctx.dp_axes, ctx.axis_model, None) if ctx.sp_residual
                else _P(ctx.dp_axes, None, None))
    x = ctx.constraint(x, res_spec)
    h = rms_norm(x, p["ln2"], cfg.rms_norm_eps)
    stats = None
    if "moe" in p:
        y, stats = _moe_apply(p["moe"], h.reshape(B * S, d), cfg, ctx)
        h = y.reshape(B, S, d)
    else:
        h = mlp(p["mlp"], h, cfg.activation)
    x = x + h
    # sequence-parallel residual: the carry saved per layer for backward is
    # 1/16 the size; attention/FFN internally gather (§Perf iter 3)
    x = ctx.constraint(x, res_spec)
    return x, stats


def _block_decode(p: Dict, cfg: ModelConfig, x: jax.Array,
                  cache: kvc.KVCache, ctx: ParallelCtx, *,
                  is_local: bool = False, mrope_positions=None
                  ) -> Tuple[jax.Array, kvc.KVCache, Optional[MoEStats]]:
    B, _, d = x.shape
    h = rms_norm(x, p["ln1"], cfg.rms_norm_eps)
    if ctx.seq_shard_cache and not is_local:
        h, cache = _sp_decode_attention(p["attn"], cfg, h, cache, ctx,
                                        mrope_positions=mrope_positions)
    else:
        h, cache = attn.decode_attention(p["attn"], cfg, h, cache,
                                         is_local=is_local,
                                         mrope_positions=mrope_positions)
    x = x + h
    h = rms_norm(x, p["ln2"], cfg.rms_norm_eps)
    stats = None
    if "moe" in p:
        y, stats = _moe_apply(p["moe"], h.reshape(B, d), cfg, ctx)
        h = y.reshape(B, 1, d)
    else:
        h = mlp(p["mlp"], h, cfg.activation)
    x = x + h
    return x, cache, stats


# ---------------------------------------------------------------------------
# Sequence-parallel decode attention (long-context: cache sharded over seq)
# ---------------------------------------------------------------------------

def _sp_decode_attention(params, cfg: ModelConfig, x: jax.Array,
                         cache: kvc.KVCache, ctx: ParallelCtx, *,
                         mrope_positions=None):
    """Flash-decode with the KV cache sharded along slots.

    Slot axes come from ``ctx.seq_shard_axes`` (default: the data axes —
    long-context batch-1; decode cells use ("model",) so attention weights
    stay replicated and the multi-GB cache never crosses a link).  The batch
    dim is sharded over the data axes when batch > 1 and data isn't already
    used for slots.  Inside shard_map each shard computes a partial
    (acc, m, l) over its cache slice; one tiny psum combines.  The new
    token's k/v is written only by the owning shard (one-sided, local).
    """
    if cfg.mla is not None:
        raise ValueError("sequence-sharded decode splits a GQA cache's "
                         "heads' slots; latent attention is not supported "
                         "there")
    mesh = ctx.mesh
    if mesh is None:
        return attn.decode_attention(params, cfg, x, cache,
                                     mrope_positions=mrope_positions)
    dp = ctx.seq_shard_axes or ctx.dp_axes
    B_global = x.shape[0]
    batch_axes = ctx.dp_axes if (B_global > 1 and
                                 not set(ctx.dp_axes) & set(dp)) else ()

    h_heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    n_shards = int(np.prod([mesh.shape[a] for a in dp]))
    slots_global = cache.k.shape[1]
    shard_sz = slots_global // n_shards

    def island(p, xq, ck, cv, length):
        B = xq.shape[0]
        ridx = sum(jax.lax.axis_index(a) *
                   int(np.prod([mesh.shape[b] for b in dp[i + 1:]]))
                   for i, a in enumerate(dp))
        q = attn._split_heads(xq[:, 0] @ p["wq"], h_heads, hd)[:, None]
        k = attn._split_heads(xq[:, 0] @ p["wk"], kvh, hd)[:, None]
        v = attn._split_heads(xq[:, 0] @ p["wv"], kvh, hd)[:, None]
        pos = length[:, None]
        cos, sin = attn._rope_for(cfg, pos, mrope_positions)
        q = attn.apply_rope(q, cos, sin)
        k = attn.apply_rope(k, cos, sin)
        # masked write into the owning shard
        local = pos[:, 0] - ridx * shard_sz
        ok = (local >= 0) & (local < shard_sz)
        bidx = jnp.arange(B)
        li = jnp.clip(local, 0, shard_sz - 1)
        ck = ck.at[bidx, li].set(
            jnp.where(ok[:, None, None], k[:, 0], ck[bidx, li]))
        cv = cv.at[bidx, li].set(
            jnp.where(ok[:, None, None], v[:, 0], cv[bidx, li]))
        # partial flash over the local slice: valid = global idx < length+1
        gidx = ridx * shard_sz + jnp.arange(shard_sz)
        valid = gidx[None, :] < (length + 1)[:, None]
        local_cache = kvc.KVCache(k=ck, v=cv,
                                  length=jnp.sum(valid, axis=1), window=0)
        # reuse the partial kernel path with an explicit mask via lengths:
        # valid slots are a prefix only on the owning/earlier shards, which
        # jnp.sum(valid) encodes exactly (cache is written in order).
        acc, m, l = attn.decode_attention_partial(p, cfg, q, local_cache)
        g_m = jax.lax.pmax(m, dp)
        scale = jnp.exp(m - g_m)
        num = jax.lax.psum(acc * scale, dp)
        den = jax.lax.psum(l * scale, dp)
        out = (num / jnp.maximum(den, 1e-30))            # (B,1,H,hd)
        out = out.reshape(B, 1, h_heads * hd).astype(xq.dtype) @ p["wo"]
        return out, ck, cv

    b = batch_axes if batch_axes else None
    cache_spec = _P(b, dp, None, None)
    x_spec = _P(b, None, None)
    fn = jax.shard_map(
        island, mesh=mesh,
        in_specs=({k: _P(None, None) for k in ("wq", "wk", "wv", "wo")},
                  x_spec, cache_spec, cache_spec, _P(b)),
        out_specs=(x_spec, cache_spec, cache_spec),
        check_vma=False)
    out, ck, cv = fn(params, x, cache.k, cache.v, cache.length)
    new_cache = kvc.KVCache(k=ck, v=cv, length=cache.length + 1,
                            window=cache.window)
    return out, new_cache


# ---------------------------------------------------------------------------
# Scan helpers (homogeneous stacks)
# ---------------------------------------------------------------------------

def _maybe_remat(fn, ctx: ParallelCtx):
    return jax.checkpoint(fn) if ctx.remat else fn


def _scan_train(blocks: Dict, cfg: ModelConfig, x: jax.Array,
                positions: jax.Array, ctx: ParallelCtx, *,
                is_local: bool = False, mrope=None):
    def body(xc, p):
        out, stats = _block_train(p, cfg, xc, positions, ctx,
                                  is_local=is_local, mrope_positions=mrope)
        if stats is None:
            stats = _zero_stats(cfg)
        return out, stats
    x, stats = jax.lax.scan(_maybe_remat(body, ctx), x, blocks,
                            unroll=ctx.unroll_scans)
    return x, stats


def _scan_prefill(blocks: Dict, caches, cfg: ModelConfig, x: jax.Array,
                  positions: jax.Array, ctx: ParallelCtx, *,
                  is_local: bool = False, mrope=None):
    def body(xc, inp):
        p, c = inp
        out, nc, stats = _block_prefill(p, cfg, xc, positions, c, ctx,
                                        is_local=is_local,
                                        mrope_positions=mrope)
        if stats is None:
            stats = _zero_stats(cfg)
        return out, (nc, stats)
    x, (ncaches, stats) = jax.lax.scan(body, x, (blocks, caches),
                                       unroll=ctx.unroll_scans)
    return x, ncaches, stats


def _scan_decode(blocks: Dict, caches, cfg: ModelConfig, x: jax.Array,
                 ctx: ParallelCtx, *, is_local: bool = False, mrope=None):
    def body(xc, inp):
        p, c = inp
        out, nc, stats = _block_decode(p, cfg, xc, c, ctx,
                                       is_local=is_local,
                                       mrope_positions=mrope)
        if stats is None:
            stats = _zero_stats(cfg)
        return out, (nc, stats)
    x, (ncaches, stats) = jax.lax.scan(body, x, (blocks, caches),
                                       unroll=ctx.unroll_scans)
    return x, ncaches, stats


def _zero_stats(cfg: ModelConfig) -> MoEStats:
    E = cfg.moe.num_experts if cfg.moe else 1
    z, zi = jnp.zeros(()), jnp.zeros((), jnp.int32)
    return MoEStats(aux_loss=z, z_loss=z, dropped=zi, miss=zi,
                    expert_load=jnp.zeros((E,), jnp.int32),
                    gemm_tiles_live=zi, gemm_tiles=zi)


def _sum_stats(*stats_list) -> MoEStats:
    """Reduce *stacked* per-layer MoEStats (every field has a leading layer
    dim — scan ys, or a single block's stats wrapped with ``a[None]``)."""
    acc = None
    for st in stats_list:
        if st is None:
            continue
        red = MoEStats(*[jnp.sum(v, axis=0) for v in st])
        acc = red if acc is None else MoEStats(
            *[a + b for a, b in zip(acc, red)])
    if acc is None:
        z, zi = jnp.zeros(()), jnp.zeros((), jnp.int32)
        acc = MoEStats(z, z, zi, zi, jnp.zeros((1,), jnp.int32), zi, zi)
    return acc


def _stack_one(st: Optional[MoEStats], cfg: ModelConfig) -> MoEStats:
    """Wrap a single (unrolled) block's stats with a layer dim."""
    if st is None:
        st = _zero_stats(cfg)
    return MoEStats(*[v[None] for v in st])


def _block_prefill(p: Dict, cfg: ModelConfig, x: jax.Array,
                   positions: jax.Array, cache: kvc.KVCache,
                   ctx: ParallelCtx, *, is_local: bool = False,
                   mrope_positions=None):
    B, S, d = x.shape
    h = rms_norm(x, p["ln1"], cfg.rms_norm_eps)
    h, (k, v) = attn.full_attention(
        p["attn"], cfg, h, positions, is_local=is_local,
        mrope_positions=mrope_positions, return_kv=True,
        unroll=ctx.unroll_scans)
    cache = kvc.write_prefill(cache, k, v)
    x = x + h
    h = rms_norm(x, p["ln2"], cfg.rms_norm_eps)
    stats = None
    if "moe" in p:
        y, stats = _moe_apply(p["moe"], h.reshape(B * S, d), cfg, ctx)
        h = y.reshape(B, S, d)
    else:
        h = mlp(p["mlp"], h, cfg.activation)
    x = x + h
    return x, cache, stats


def _block_prefill_chunk(p: Dict, cfg: ModelConfig, x: jax.Array,
                         positions: jax.Array, cache: kvc.KVCache,
                         ctx: ParallelCtx, *, mrope_positions=None):
    """One block over a prompt *chunk*: attention against the cache (which
    holds every earlier chunk), chunk K/V written in.  Same math as
    :func:`_block_prefill` restricted to the chunk's rows."""
    B, S, d = x.shape
    h = rms_norm(x, p["ln1"], cfg.rms_norm_eps)
    h, cache = attn.chunk_attention(p["attn"], cfg, h, cache, positions,
                                    mrope_positions=mrope_positions)
    x = x + h
    h = rms_norm(x, p["ln2"], cfg.rms_norm_eps)
    stats = None
    if "moe" in p:
        y, stats = _moe_apply(p["moe"], h.reshape(B * S, d), cfg, ctx)
        h = y.reshape(B, S, d)
    else:
        h = mlp(p["mlp"], h, cfg.activation)
    x = x + h
    return x, cache, stats


def _scan_prefill_chunk(blocks: Dict, caches, cfg: ModelConfig, x: jax.Array,
                        positions: jax.Array, ctx: ParallelCtx, *,
                        mrope=None):
    def body(xc, inp):
        p, c = inp
        out, nc, stats = _block_prefill_chunk(p, cfg, xc, positions, c, ctx,
                                              mrope_positions=mrope)
        if stats is None:
            stats = _zero_stats(cfg)
        return out, (nc, stats)
    x, (ncaches, stats) = jax.lax.scan(body, x, (blocks, caches),
                                       unroll=ctx.unroll_scans)
    return x, ncaches, stats


# ---------------------------------------------------------------------------
# Embedding / head / loss
# ---------------------------------------------------------------------------

def _embed_tokens(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                  ctx: ParallelCtx) -> jax.Array:
    e = jnp.take(params["embed"], tokens, axis=0)
    return ctx.constraint(e, _P(ctx.dp_axes, None, None))


def _head_weight(params: Dict, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["head"]


def _logits(params: Dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    logits = x @ _head_weight(params, cfg)
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    return logits


def _head(params: Dict, cfg: ModelConfig, x: jax.Array, at: int
          ) -> jax.Array:
    """The served head, in the ``head`` stage: the final norm, then fp32
    logits at sequence position ``at``."""
    with tracing.stage("head"):
        x = rms_norm(x, params["final_ln"], cfg.rms_norm_eps)
        return _logits(params, cfg, x[:, at]).astype(jnp.float32)


def chunked_cross_entropy(params: Dict, cfg: ModelConfig, x: jax.Array,
                          labels: jax.Array, ctx: ParallelCtx) -> jax.Array:
    """CE without materializing (B, S, V) logits: scan + remat over seq
    chunks; vocab stays sharded (one-hot contraction, no vocab gather)."""
    B, S, d = x.shape
    V = cfg.padded_vocab
    chunk = min(ctx.ce_chunk, S)
    while S % chunk:
        chunk //= 2
    nc = S // chunk
    xc = x.reshape(B, nc, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(B, nc, chunk).swapaxes(0, 1)

    def body(tot, inp):
        xi, li = inp
        logits = _logits(params, cfg, xi).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(li, V, dtype=jnp.float32)
        gold = jnp.einsum("bcv,bcv->bc", logits, onehot)
        return tot + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                          (xc, lc), unroll=ctx.unroll_scans)
    return tot / (B * S)


# ---------------------------------------------------------------------------
# Family builders
# ---------------------------------------------------------------------------

def _vmap_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


class Model(NamedTuple):
    cfg: ModelConfig
    init_params: Callable
    loss_fn: Callable          # (params, batch, ctx) -> (loss, metrics)
    prefill: Callable          # (params, tokens, ctx, extras) -> (logits, cache)
    decode_step: Callable      # (params, token, cache, ctx, extras) -> (logits, cache)
    init_cache: Callable       # (batch, max_slots, abstract=False) -> cache
    num_servers: int
    # chunked-prefill continuation: (params, tokens, cache, start, ctx) ->
    # (logits, cache).  None for families without cache-resident prefill
    # (the serving scheduler falls back to whole-prompt prefill).
    prefill_chunk: Optional[Callable] = None
    # batch axis shared by every cache leaf (for microbatch splits in the
    # serving executor); None when the cache layout is heterogeneous.
    cache_batch_axis: Optional[int] = None
    # block-pool cache builder: (num_blocks, block_size, batch, max_slots,
    # abstract=False) -> cache pytree of stacked PagedKVCaches.  None for
    # families without paged-cache support (ring caches, recurrent states).
    init_paged_cache: Optional[Callable] = None


def _positions(tokens: jax.Array) -> jax.Array:
    return jnp.arange(tokens.shape[1], dtype=jnp.int32)


def _mrope_from_batch(cfg, batch, tokens):
    if cfg.mrope_sections is None:
        return None
    mp = batch.get("mrope_positions") if isinstance(batch, dict) else None
    if mp is None:
        pos = jnp.broadcast_to(_positions(tokens)[None],
                               (tokens.shape[0], tokens.shape[1]))
        return text_mrope_positions(pos)
    return mp


# --------------------------------------------------- decoder-only (all LM)

def build_model(cfg: ModelConfig, num_servers: int = 1,
                redundant_table=None) -> Model:
    """Dispatch to the family builder."""
    if cfg.family == "hybrid":
        return _build_zamba(cfg)
    if cfg.family == "ssm":
        return _build_rwkv(cfg)
    if cfg.family == "audio":
        return _build_encdec(cfg)
    if cfg.local_global_pattern:
        return _build_local_global(cfg)
    return _build_decoder(cfg, num_servers, redundant_table)


def _build_decoder(cfg: ModelConfig, num_servers: int,
                   redundant_table) -> Model:
    """Uniform decoder (+ optional dense prefix for first_k_dense MoE)."""
    m = cfg.moe
    n_dense_prefix = m.first_k_dense if m else 0
    n_main = cfg.num_layers - n_dense_prefix
    main_is_moe = m is not None
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    def init_params(key):
        ks = jax.random.split(key, 5)
        p = {
            "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model, dt),
            "final_ln": rms_norm_init(cfg.d_model),
            "blocks": _vmap_init(
                lambda k: _init_block(k, cfg, main_is_moe, num_servers,
                                      redundant_table),
                ks[1], n_main),
        }
        if n_dense_prefix:
            p["dense_blocks"] = _vmap_init(
                lambda k: _init_block(k, cfg, False, num_servers, None),
                ks[2], n_dense_prefix)
        if not cfg.tie_embeddings:
            from repro.models.common import dense_init
            p["head"] = dense_init(ks[3], cfg.d_model, cfg.padded_vocab, dt)
        return p

    def loss_fn(params, batch, ctx: ParallelCtx):
        tokens, labels = batch["tokens"], batch["labels"]
        x = _embed_tokens(params, cfg, tokens, ctx)
        pos = _positions(tokens)
        mrope = _mrope_from_batch(cfg, batch, tokens)
        stats_all = []
        if n_dense_prefix:
            x, st = _scan_train(params["dense_blocks"], cfg, x, pos, ctx,
                                mrope=mrope)
            stats_all.append(st)
        x, st = _scan_train(params["blocks"], cfg, x, pos, ctx, mrope=mrope)
        stats_all.append(st)
        x = rms_norm(x, params["final_ln"], cfg.rms_norm_eps)
        ce = chunked_cross_entropy(params, cfg, x, labels, ctx)
        stats = _sum_stats(*stats_all)
        loss = ce + stats.aux_loss + stats.z_loss
        return loss, {"ce": ce, "aux": stats.aux_loss,
                      "dropped": stats.dropped, "miss": stats.miss,
                      "expert_load": stats.expert_load}

    kv_heads, k_dim, v_dim = _cache_dims(cfg)

    def init_cache(batch: int, max_slots: int, abstract: bool = False):
        def stack(n):
            return _stack_kv_cache(n, batch, max_slots, kv_heads, k_dim, dt,
                                   abstract=abstract, v_head_dim=v_dim)
        cache = {"blocks": stack(n_main)}
        if n_dense_prefix:
            cache["dense"] = stack(n_dense_prefix)
        return cache

    def init_paged_cache(num_blocks: int, block_size: int, batch: int,
                         max_slots: int, abstract: bool = False):
        """Block-pool cache: every layer gets its own pool; one logical
        block id addresses the same slot of every layer's pool, so the host
        keeps a single block table per sequence."""
        assert max_slots % block_size == 0, (max_slots, block_size)

        def stack(n):
            return _stack_paged_kv_cache(
                n, num_blocks, block_size, batch, max_slots // block_size,
                kv_heads, k_dim, dt, abstract=abstract, v_head_dim=v_dim)
        cache = {"blocks": stack(n_main)}
        if n_dense_prefix:
            cache["dense"] = stack(n_dense_prefix)
        return cache

    def prefill(params, tokens, ctx: ParallelCtx, batch=None,
                max_slots: Optional[int] = None):
        B, S = tokens.shape
        cache = init_cache(B, max_slots or S)
        x = _embed_tokens(params, cfg, tokens, ctx)
        pos = _positions(tokens)
        mrope = _mrope_from_batch(cfg, batch or {}, tokens)
        if n_dense_prefix:
            x, cd, _ = _scan_prefill(params["dense_blocks"], cache["dense"],
                                     cfg, x, pos, ctx, mrope=mrope)
            cache["dense"] = cd
        x, cb, _ = _scan_prefill(params["blocks"], cache["blocks"], cfg, x,
                                 pos, ctx, mrope=mrope)
        cache["blocks"] = cb
        logits = _head(params, cfg, x, -1)
        return logits, cache

    def prefill_chunk(params, tokens, cache, start, ctx: ParallelCtx,
                      batch=None):
        """Continue a prefill: process prompt positions [start, start+C)
        against a cache already holding [0, start).  Composing chunks over a
        prompt reproduces :func:`prefill`'s logits and cache exactly (same
        rotated keys, same masked softmax — padding lanes are exact zeros).

        Also returns the chunk's :class:`MoEStats` (summed over layers) so
        chunked prefill feeds ``expert_load`` into the traffic EMA exactly
        like decode steps do — long-prompt-heavy workloads rebalance from
        prompt traffic, not just decode traffic.
        """
        B, C = tokens.shape
        start = jnp.asarray(start, jnp.int32)
        pos = start + jnp.arange(C, dtype=jnp.int32)
        x = _embed_tokens(params, cfg, tokens, ctx)
        mrope = None
        if cfg.mrope_sections is not None:
            mrope = text_mrope_positions(
                jnp.broadcast_to(pos[None], (B, C)))
        stats_all = []
        if n_dense_prefix:
            x, cd, st = _scan_prefill_chunk(params["dense_blocks"],
                                            cache["dense"], cfg, x, pos, ctx,
                                            mrope=mrope)
            cache = dict(cache, dense=cd)
            stats_all.append(st)
        x, cb, st = _scan_prefill_chunk(params["blocks"], cache["blocks"],
                                        cfg, x, pos, ctx, mrope=mrope)
        cache = dict(cache, blocks=cb)
        stats_all.append(st)
        logits = _head(params, cfg, x, -1)
        return logits, cache, _sum_stats(*stats_all)

    def decode_step(params, token, cache, ctx: ParallelCtx, batch=None):
        x = _embed_tokens(params, cfg, token, ctx)
        stats_all = []
        if n_dense_prefix:
            x, cd, st = _scan_decode(params["dense_blocks"], cache["dense"],
                                     cfg, x, ctx)
            cache = dict(cache, dense=cd)
            stats_all.append(st)
        x, cb, st = _scan_decode(params["blocks"], cache["blocks"], cfg, x, ctx)
        cache = dict(cache, blocks=cb)
        stats_all.append(st)
        logits = _head(params, cfg, x, 0)
        return logits, cache, _sum_stats(*stats_all)

    return Model(cfg, init_params, loss_fn, prefill, decode_step, init_cache,
                 num_servers, prefill_chunk=prefill_chunk,
                 cache_batch_axis=1, init_paged_cache=init_paged_cache)


def _cache_dims(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(heads, k dim, v dim) of one layer's cache: latent attention keeps
    one head of its normalised latent in k and of its rotary key in v."""
    if cfg.mla is not None:
        return 1, cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
    return cfg.num_kv_heads, cfg.head_dim, cfg.head_dim


def _stack_kv_cache(n: int, batch: int, max_slots: int, kv_heads: int,
                    head_dim: int, dtype, *, window: int = 0,
                    abstract: bool = False,
                    v_head_dim: int = 0) -> kvc.KVCache:
    """A stacked (n, ...) KVCache for scan-over-layers stacks."""
    mk = kvc.kv_cache_spec if abstract else kvc.init_kv_cache
    c = mk(batch, max_slots, kv_heads, head_dim, dtype, window=window,
           v_head_dim=v_head_dim)
    if abstract:
        lift = lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype)
    else:
        lift = lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy()
    return kvc.KVCache(k=lift(c.k), v=lift(c.v), length=lift(c.length),
                       window=c.window)


def _stack_paged_kv_cache(n: int, num_blocks: int, block_size: int,
                          batch: int, max_blocks: int, kv_heads: int,
                          head_dim: int, dtype, *,
                          abstract: bool = False,
                          v_head_dim: int = 0) -> kvc.PagedKVCache:
    """A stacked (n, ...) PagedKVCache for scan-over-layers stacks.

    Block tables / lengths are broadcast per layer so every leaf carries the
    leading layer dim the scan needs; the executor rewrites them from the
    host-side pool each step."""
    mk = kvc.paged_kv_cache_spec if abstract else kvc.init_paged_kv_cache
    c = mk(num_blocks, block_size, batch, max_blocks, kv_heads, head_dim,
           dtype, v_head_dim=v_head_dim)
    if abstract:
        lift = lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype)
    else:
        lift = lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy()
    return kvc.PagedKVCache(k=lift(c.k), v=lift(c.v),
                            block_tables=lift(c.block_tables),
                            length=lift(c.length), block_size=c.block_size)


# --------------------------------------------------- gemma3: 5 local : 1 global

def _build_local_global(cfg: ModelConfig) -> Model:
    """gemma3 family: groups of (pattern local layers + 1 global layer),
    plus a trailing remainder of local layers.  Local layers keep only a
    ``sliding_window``-slot ring cache."""
    pat = cfg.local_global_pattern
    group = pat + 1
    n_groups = cfg.num_layers // group
    n_rem = cfg.num_layers - n_groups * group
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    def init_params(key):
        ks = jax.random.split(key, 5)
        def group_init(k):
            k1, k2 = jax.random.split(k)
            return {
                "local": _vmap_init(
                    lambda kk: _init_block(kk, cfg, False, 1, None), k1, pat),
                "global": _init_block(k2, cfg, False, 1, None),
            }
        p = {
            "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model, dt),
            "final_ln": rms_norm_init(cfg.d_model),
            "groups": _vmap_init(group_init, ks[1], n_groups),
        }
        if n_rem:
            p["rem_local"] = _vmap_init(
                lambda k: _init_block(k, cfg, False, 1, None), ks[2], n_rem)
        if not cfg.tie_embeddings:
            from repro.models.common import dense_init
            p["head"] = dense_init(ks[3], cfg.d_model, cfg.padded_vocab, dt)
        return p

    def loss_fn(params, batch, ctx: ParallelCtx):
        tokens, labels = batch["tokens"], batch["labels"]
        x = _embed_tokens(params, cfg, tokens, ctx)
        pos = _positions(tokens)

        def group_body(xc, gp):
            xc, _ = _scan_train(gp["local"], cfg, xc, pos, ctx, is_local=True)
            xc, _ = _block_train(gp["global"], cfg, xc, pos, ctx,
                                 is_local=False)
            return xc, jnp.zeros(())

        x, _ = jax.lax.scan(_maybe_remat(group_body, ctx), x,
                            params["groups"], unroll=ctx.unroll_scans)
        if n_rem:
            x, _ = _scan_train(params["rem_local"], cfg, x, pos, ctx,
                               is_local=True)
        x = rms_norm(x, params["final_ln"], cfg.rms_norm_eps)
        ce = chunked_cross_entropy(params, cfg, x, labels, ctx)
        return ce, {"ce": ce}

    def init_cache(batch: int, max_slots: int, abstract: bool = False):
        w = cfg.sliding_window
        local = _stack_kv_cache(pat, batch, max_slots, cfg.num_kv_heads,
                                cfg.head_dim, dt, window=w, abstract=abstract)
        local = jax.tree.map(
            lambda a: (jax.ShapeDtypeStruct((n_groups,) + a.shape, a.dtype)
                       if abstract else
                       jnp.broadcast_to(a[None], (n_groups,) + a.shape).copy()),
            local)
        glob = _stack_kv_cache(n_groups, batch, max_slots, cfg.num_kv_heads,
                               cfg.head_dim, dt, abstract=abstract)
        cache = {"local": local, "global": glob}
        if n_rem:
            cache["rem"] = _stack_kv_cache(
                n_rem, batch, max_slots, cfg.num_kv_heads, cfg.head_dim, dt,
                window=w, abstract=abstract)
        return cache

    def prefill(params, tokens, ctx: ParallelCtx, batch=None,
                max_slots: Optional[int] = None):
        B, S = tokens.shape
        cache = init_cache(B, max_slots or S)
        x = _embed_tokens(params, cfg, tokens, ctx)
        pos = _positions(tokens)

        def group_body(xc, inp):
            gp, cl, cg = inp
            xc, cl, _ = _scan_prefill(gp["local"], cl, cfg, xc, pos, ctx,
                                      is_local=True)
            xc, cg, _ = _block_prefill(gp["global"], cfg, xc, pos, cg, ctx)
            return xc, (cl, cg)

        x, (cl, cg) = jax.lax.scan(
            group_body, x, (params["groups"], cache["local"],
                            cache["global"]), unroll=ctx.unroll_scans)
        cache["local"], cache["global"] = cl, cg
        if n_rem:
            x, cr, _ = _scan_prefill(params["rem_local"], cache["rem"], cfg,
                                     x, pos, ctx, is_local=True)
            cache["rem"] = cr
        return _head(params, cfg, x, -1), cache

    def decode_step(params, token, cache, ctx: ParallelCtx, batch=None):
        x = _embed_tokens(params, cfg, token, ctx)

        def group_body(xc, inp):
            gp, cl, cg = inp
            xc, cl, _ = _scan_decode(gp["local"], cl, cfg, xc, ctx,
                                     is_local=True)
            xc, cg, _ = _block_decode(gp["global"], cfg, xc, cg, ctx)
            return xc, (cl, cg)

        x, (cl, cg) = jax.lax.scan(
            group_body, x, (params["groups"], cache["local"],
                            cache["global"]), unroll=ctx.unroll_scans)
        cache = dict(cache, local=cl, **{"global": cg})
        if n_rem:
            x, cr, _ = _scan_decode(params["rem_local"], cache["rem"], cfg,
                                    x, ctx, is_local=True)
            cache["rem"] = cr
        logits = _head(params, cfg, x, 0)
        return logits, cache, None

    return Model(cfg, init_params, loss_fn, prefill, decode_step, init_cache, 1)


# --------------------------------------------------- zamba2 hybrid

def _build_zamba(cfg: ModelConfig) -> Model:
    """zamba2: groups of (shared_block_every mamba layers + the SHARED
    attention block).  The shared block's params are reused by every group
    (zamba's signature trick); each application keeps its own KV cache."""
    per = cfg.shared_block_every
    n_groups = cfg.num_layers // per
    assert n_groups * per == cfg.num_layers, (cfg.num_layers, per)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    def init_mamba_layer(k):
        return {"ln": rms_norm_init(cfg.d_model),
                "mamba": mam.init_mamba(k, cfg)}

    def init_params(key):
        ks = jax.random.split(key, 5)
        p = {
            "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model, dt),
            "final_ln": rms_norm_init(cfg.d_model),
            "mamba": jax.vmap(lambda k: jax.vmap(init_mamba_layer)(
                jax.random.split(k, per)))(jax.random.split(ks[1], n_groups)),
            "shared": _init_block(ks[2], cfg, False, 1, None),
        }
        if not cfg.tie_embeddings:
            from repro.models.common import dense_init
            p["head"] = dense_init(ks[3], cfg.d_model, cfg.padded_vocab, dt)
        return p

    def _mamba_scan_fwd(layers, cfg_, x, states):
        def body(xc, inp):
            lp, st = inp
            h = rms_norm(xc, lp["ln"], cfg_.rms_norm_eps)
            y, nst = mam.mamba_forward(lp["mamba"], cfg_, h, st)
            return xc + y, nst
        return jax.lax.scan(body, x, (layers, states))

    def _mamba_scan_dec(layers, cfg_, x, states):
        def body(xc, inp):
            lp, st = inp
            h = rms_norm(xc, lp["ln"], cfg_.rms_norm_eps)
            y, nst = mam.mamba_decode(lp["mamba"], cfg_, h, st)
            return xc + y, nst
        return jax.lax.scan(body, x, (layers, states))

    def _states(batch: int, abstract: bool, ctx: ParallelCtx = None):
        st = mam.init_mamba_state(cfg, batch)
        if abstract:
            lift = lambda a: jax.ShapeDtypeStruct(
                (n_groups, per) + a.shape, a.dtype)
        else:
            lift = lambda a: jnp.broadcast_to(
                a[None, None], (n_groups, per) + a.shape).copy()
        st = jax.tree.map(lift, st)
        if ctx is not None and ctx.mesh is not None and not abstract:
            st = mam.MambaState(
                ssm=ctx.constraint(st.ssm,
                                   _P(None, None, ctx.dp_axes,
                                      ctx.axis_model, None, None)),
                conv=ctx.constraint(st.conv,
                                    _P(None, None, ctx.dp_axes, None,
                                       ctx.axis_model)),
            )
        return st

    def init_cache(batch: int, max_slots: int, abstract: bool = False):
        return {
            "mamba": _states(batch, abstract),
            "shared": _stack_kv_cache(n_groups, batch, max_slots,
                                      cfg.num_kv_heads, cfg.head_dim, dt,
                                      abstract=abstract),
        }

    def loss_fn(params, batch, ctx: ParallelCtx):
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        x = _embed_tokens(params, cfg, tokens, ctx)
        pos = _positions(tokens)
        zero_states = _states(B, False, ctx)

        def group_body(xc, inp):
            layers, sts = inp
            xc, _ = _mamba_scan_fwd(layers, cfg, xc, sts)
            xc, _ = _block_train(params["shared"], cfg, xc, pos, ctx)
            return xc, jnp.zeros(())

        x, _ = jax.lax.scan(_maybe_remat(group_body, ctx), x,
                            (params["mamba"], zero_states),
                            unroll=ctx.unroll_scans)
        x = rms_norm(x, params["final_ln"], cfg.rms_norm_eps)
        ce = chunked_cross_entropy(params, cfg, x, labels, ctx)
        return ce, {"ce": ce}

    def prefill(params, tokens, ctx: ParallelCtx, batch=None,
                max_slots: Optional[int] = None):
        B, S = tokens.shape
        cache = init_cache(B, max_slots or S)
        x = _embed_tokens(params, cfg, tokens, ctx)
        pos = _positions(tokens)

        def group_body(xc, inp):
            layers, sts, ckv = inp
            xc, nsts = _mamba_scan_fwd(layers, cfg, xc, sts)
            xc, ckv, _ = _block_prefill(params["shared"], cfg, xc, pos, ckv,
                                        ctx)
            return xc, (nsts, ckv)

        x, (nst, ckv) = jax.lax.scan(
            group_body, x, (params["mamba"], cache["mamba"], cache["shared"]),
            unroll=ctx.unroll_scans)
        cache = {"mamba": nst, "shared": ckv}
        return _head(params, cfg, x, -1), cache

    def decode_step(params, token, cache, ctx: ParallelCtx, batch=None):
        x = _embed_tokens(params, cfg, token, ctx)

        def group_body(xc, inp):
            layers, sts, ckv = inp
            xc, nsts = _mamba_scan_dec(layers, cfg, xc, sts)
            xc, ckv, _ = _block_decode(params["shared"], cfg, xc, ckv, ctx)
            return xc, (nsts, ckv)

        x, (nst, ckv) = jax.lax.scan(
            group_body, x, (params["mamba"], cache["mamba"], cache["shared"]),
            unroll=ctx.unroll_scans)
        cache = {"mamba": nst, "shared": ckv}
        logits = _head(params, cfg, x, 0)
        return logits, cache, None

    return Model(cfg, init_params, loss_fn, prefill, decode_step, init_cache, 1)


# --------------------------------------------------- rwkv6

def _build_rwkv(cfg: ModelConfig) -> Model:
    L = cfg.num_layers
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    def layer_init(k):
        k1, k2 = jax.random.split(k)
        return {
            "ln1": rms_norm_init(cfg.d_model),
            "ln2": rms_norm_init(cfg.d_model),
            "tmix": rwk.init_rwkv_tmix(k1, cfg),
            "cmix": rwk.init_rwkv_cmix(k2, cfg),
        }

    def init_params(key):
        ks = jax.random.split(key, 4)
        p = {
            "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model, dt),
            "final_ln": rms_norm_init(cfg.d_model),
            "blocks": _vmap_init(layer_init, ks[1], L),
        }
        if not cfg.tie_embeddings:
            from repro.models.common import dense_init
            p["head"] = dense_init(ks[2], cfg.d_model, cfg.padded_vocab, dt)
        return p

    def _states(batch: int, abstract: bool, ctx: ParallelCtx = None):
        st = rwk.init_rwkv_state(cfg, batch)
        if abstract:
            lift = lambda a: jax.ShapeDtypeStruct((L,) + a.shape, a.dtype)
        else:
            lift = lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy()
        st = jax.tree.map(lift, st)
        if ctx is not None and ctx.mesh is not None and not abstract:
            # the wkv carry drives the sharding of the whole recurrence:
            # heads over model, batch over data (§Perf iter 2)
            st = rwk.RwkvState(
                wkv=ctx.constraint(st.wkv,
                                   _P(None, ctx.dp_axes, ctx.axis_model,
                                      None, None)),
                shift_tmix=ctx.constraint(st.shift_tmix,
                                          _P(None, ctx.dp_axes, None)),
                shift_cmix=ctx.constraint(st.shift_cmix,
                                          _P(None, ctx.dp_axes, None)),
            )
        return st

    def init_cache(batch: int, max_slots: int, abstract: bool = False):
        return {"states": _states(batch, abstract)}

    def _forward(params, x, states, ctx):
        if ctx.mesh is not None:
            # explicit Megatron-TP island: one psum per sub-layer
            layer = rwk.rwkv_block_spmd(cfg, ctx.mesh, ctx.dp_axes,
                                        ctx.axis_model)

            def body(xc, inp):
                p, st = inp
                xc, S, sh_t, sh_c = layer(p["tmix"], p["cmix"], p["ln1"],
                                          p["ln2"], xc, st.wkv,
                                          st.shift_tmix, st.shift_cmix)
                return xc, rwk.RwkvState(S, sh_t, sh_c)
        else:
            def body(xc, inp):
                p, st = inp
                xc, nst = rwk.rwkv_block_forward(
                    p["tmix"], p["cmix"], cfg, xc, st,
                    (p["ln1"], p["ln2"]))
                return xc, nst
        return jax.lax.scan(body, x, (params["blocks"], states),
                            unroll=ctx.unroll_scans)

    def loss_fn(params, batch, ctx: ParallelCtx):
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        x = _embed_tokens(params, cfg, tokens, ctx)
        x, _ = _forward(params, x, _states(B, False, ctx), ctx)
        x = rms_norm(x, params["final_ln"], cfg.rms_norm_eps)
        ce = chunked_cross_entropy(params, cfg, x, labels, ctx)
        return ce, {"ce": ce}

    def prefill(params, tokens, ctx: ParallelCtx, batch=None,
                max_slots: Optional[int] = None):
        B, S = tokens.shape
        x = _embed_tokens(params, cfg, tokens, ctx)
        x, nst = _forward(params, x, _states(B, False, ctx), ctx)
        return _head(params, cfg, x, -1), {"states": nst}

    def decode_step(params, token, cache, ctx: ParallelCtx, batch=None):
        x = _embed_tokens(params, cfg, token, ctx)

        def body(xc, inp):
            p, st = inp
            xc, nst = rwk.rwkv_block_decode(
                p["tmix"], p["cmix"], cfg, xc, st, (p["ln1"], p["ln2"]))
            return xc, nst
        x, nst = jax.lax.scan(body, x, (params["blocks"], cache["states"]),
                              unroll=ctx.unroll_scans)
        logits = _head(params, cfg, x, 0)
        return logits, {"states": nst}, None

    return Model(cfg, init_params, loss_fn, prefill, decode_step, init_cache, 1)


# --------------------------------------------------- whisper enc-dec

def _build_encdec(cfg: ModelConfig) -> Model:
    """whisper-base backbone.  The conv/mel frontend is a stub: batches carry
    precomputed frame embeddings (B, encoder_seq_len, d_model)."""
    Le, Ld = cfg.num_encoder_layers, cfg.num_layers
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    def enc_layer_init(k):
        return _init_block(k, cfg, False, 1, None)

    def dec_layer_init(k):
        ks = jax.random.split(k, 2)
        p = _init_block(ks[0], cfg, False, 1, None)
        p["ln_x"] = rms_norm_init(cfg.d_model)
        p["cross"] = attn.init_cross_attention(ks[1], cfg)
        return p

    def init_params(key):
        ks = jax.random.split(key, 5)
        return {
            "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model, dt),
            "final_ln": rms_norm_init(cfg.d_model),
            "encoder": _vmap_init(enc_layer_init, ks[1], Le),
            "decoder": _vmap_init(dec_layer_init, ks[2], Ld),
        }

    def _encode(params, frames, ctx):
        pos = jnp.arange(frames.shape[1], dtype=jnp.int32)

        def body(xc, p):
            h = rms_norm(xc, p["ln1"], cfg.rms_norm_eps)
            h = attn.full_attention(p["attn"], cfg, h, pos, causal=False,
                                    unroll=ctx.unroll_scans)
            xc = xc + h
            h = rms_norm(xc, p["ln2"], cfg.rms_norm_eps)
            return xc + mlp(p["mlp"], h, cfg.activation), jnp.zeros(())

        x, _ = jax.lax.scan(body, frames.astype(dt), params["encoder"],
                            unroll=ctx.unroll_scans)
        return x

    def _dec_block_train(p, x, enc_out, pos, ctx):
        h = rms_norm(x, p["ln1"], cfg.rms_norm_eps)
        h = attn.full_attention(p["attn"], cfg, h, pos,
                                unroll=ctx.unroll_scans)
        x = x + h
        h = rms_norm(x, p["ln_x"], cfg.rms_norm_eps)
        x = x + attn.cross_attention(p["cross"], cfg, h, enc_out)
        h = rms_norm(x, p["ln2"], cfg.rms_norm_eps)
        return x + mlp(p["mlp"], h, cfg.activation)

    def loss_fn(params, batch, ctx: ParallelCtx):
        tokens, labels = batch["tokens"], batch["labels"]
        frames = batch["frames"]
        enc_out = _encode(params, frames, ctx)
        x = _embed_tokens(params, cfg, tokens, ctx)
        pos = _positions(tokens)

        def body(xc, p):
            return _dec_block_train(p, xc, enc_out, pos, ctx), jnp.zeros(())

        x, _ = jax.lax.scan(_maybe_remat(body, ctx), x, params["decoder"],
                            unroll=ctx.unroll_scans)
        x = rms_norm(x, params["final_ln"], cfg.rms_norm_eps)
        ce = chunked_cross_entropy(params, cfg, x, labels, ctx)
        return ce, {"ce": ce}

    def init_cache(batch: int, max_slots: int, abstract: bool = False):
        self_kv = _stack_kv_cache(Ld, batch, max_slots, cfg.num_kv_heads,
                                  cfg.head_dim, dt, abstract=abstract)
        shape = (Ld, batch, cfg.encoder_seq_len, cfg.num_kv_heads,
                 cfg.head_dim)
        if abstract:
            ck = jax.ShapeDtypeStruct(shape, dt)
            cv = jax.ShapeDtypeStruct(shape, dt)
        else:
            ck = jnp.zeros(shape, dt)
            cv = jnp.zeros(shape, dt)
        return {"self": self_kv, "cross_k": ck, "cross_v": cv}

    def prefill(params, tokens, ctx: ParallelCtx, batch=None,
                max_slots: Optional[int] = None):
        """Encodes frames, caches cross-attention K/V, prefills decoder."""
        B, S = tokens.shape
        frames = (batch or {}).get("frames")
        if frames is None:
            frames = jnp.zeros((B, cfg.encoder_seq_len, cfg.d_model), dt)
        enc_out = _encode(params, frames, ctx)
        cache = init_cache(B, max_slots or S)
        x = _embed_tokens(params, cfg, tokens, ctx)
        pos = _positions(tokens)

        def body(xc, inp):
            p, ckv = inp
            h = rms_norm(xc, p["ln1"], cfg.rms_norm_eps)
            h, (k, v) = attn.full_attention(p["attn"], cfg, h, pos,
                                            return_kv=True,
                                            unroll=ctx.unroll_scans)
            ckv = kvc.write_prefill(ckv, k, v)
            xc = xc + h
            h = rms_norm(xc, p["ln_x"], cfg.rms_norm_eps)
            xc = xc + attn.cross_attention(p["cross"], cfg, h, enc_out)
            h = rms_norm(xc, p["ln2"], cfg.rms_norm_eps)
            xc = xc + mlp(p["mlp"], h, cfg.activation)
            kx = attn._split_heads(enc_out @ p["cross"]["wk"],
                                   cfg.num_kv_heads, cfg.head_dim)
            vx = attn._split_heads(enc_out @ p["cross"]["wv"],
                                   cfg.num_kv_heads, cfg.head_dim)
            return xc, (ckv, kx, vx)

        x, (self_kv, ck, cv) = jax.lax.scan(
            body, x, (params["decoder"], cache["self"]),
            unroll=ctx.unroll_scans)
        cache = {"self": self_kv, "cross_k": ck, "cross_v": cv}
        return _head(params, cfg, x, -1), cache

    def decode_step(params, token, cache, ctx: ParallelCtx, batch=None):
        x = _embed_tokens(params, cfg, token, ctx)

        def body(xc, inp):
            p, ckv, kx, vx = inp
            h = rms_norm(xc, p["ln1"], cfg.rms_norm_eps)
            h, ckv = attn.decode_attention(p["attn"], cfg, h, ckv)
            xc = xc + h
            h = rms_norm(xc, p["ln_x"], cfg.rms_norm_eps)
            xc = xc + attn.cross_attention_cached(p["cross"], cfg, h, kx, vx)
            h = rms_norm(xc, p["ln2"], cfg.rms_norm_eps)
            xc = xc + mlp(p["mlp"], h, cfg.activation)
            return xc, ckv

        x, self_kv = jax.lax.scan(
            body, x, (params["decoder"], cache["self"], cache["cross_k"],
                      cache["cross_v"]), unroll=ctx.unroll_scans)
        cache = dict(cache, self=self_kv)
        logits = _head(params, cfg, x, 0)
        return logits, cache, None

    return Model(cfg, init_params, loss_fn, prefill, decode_step, init_cache, 1)
