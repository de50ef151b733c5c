"""Multi-head latent attention (DeepSeek-V2/V3, arXiv:2412.19437 §2.1).

Per token: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` split per head into
``q_nope`` and a roped ``q_pe``; ``[c_kv, k_pe] = x W_kva`` with ``c_kv =
RMSNorm(c_kv)`` and ``k_pe`` roped once and shared by every head.  The
cache keeps only ``c_kv`` and ``k_pe`` (a :class:`~repro.models.kv_cache
.KVCache` or ``PagedKVCache`` whose ``k`` is one head of ``kv_lora_rank``
and ``v`` one head of ``qk_rope_head_dim``), so every serving mode handles
it as it handles a GQA cache.

Two forms of the same attention, as DeepSeek's inference code has them:

* expanded (the whole sequence, with no cache: training and whole-prompt
  prefill): ``[k_nope, v] = c_kv W_kvb`` per head, kept in float32, and
  the scores ``q_nope k_nope + q_pe k_pe``;
* absorbed (everything that reads the cache: decode and prefill chunks):
  ``q_lat = q_nope W_ukᵀ`` per head scores the cached latent directly,
  ``q_lat c_kv + q_pe k_pe``, and ``o = (p c_kv) W_uv``.  A chunk and a
  decode step then compute a position's attention lane for lane alike, so
  a request recomputed by chunks after preemption gets the cache and the
  tokens it had.

Rope rotates interleaved pairs; the softmax scale is
``qk_head_dim**-0.5`` times YaRN's ``mscale(factor, mscale_all_dim)**2``.
Everything here is traced in the ``mla`` stage
(:func:`repro.tracing.stage`): the projections, the cache write, the core
and ``W_o``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro import tracing
from repro.configs.base import ModelConfig
from repro.models import kv_cache as kvc
from repro.models.common import dense_init, rms_norm, rms_norm_init
from repro.models.rope import apply_rope, rope_cos_sin, yarn_mscale

NEG_INF = -1e30
F32 = jnp.float32


def init_mla(key, cfg: ModelConfig) -> Dict:
    d, h, m = cfg.d_model, cfg.num_heads, cfg.mla
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else F32
    ks = jax.random.split(key, 5)
    return {
        "wq_a": dense_init(ks[0], d, m.q_lora_rank, dt),
        "q_norm": rms_norm_init(m.q_lora_rank),
        "wq_b": dense_init(ks[1], m.q_lora_rank, h * m.qk_head_dim, dt),
        "wkv_a": dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim,
                            dt),
        "kv_norm": rms_norm_init(m.kv_lora_rank),
        "wkv_b": dense_init(ks[3], m.kv_lora_rank,
                            h * (m.qk_nope_head_dim + m.v_head_dim), dt),
        "wo": dense_init(ks[4], h * m.v_head_dim, d, dt),
    }


def softmax_scale(cfg: ModelConfig) -> float:
    scale = cfg.mla.qk_head_dim ** -0.5
    if cfg.rope_scaling is not None:
        ms = yarn_mscale(cfg.rope_scaling.factor,
                         cfg.rope_scaling.mscale_all_dim)
        scale *= ms * ms
    return scale


def _project(params: Dict, cfg: ModelConfig, x: jax.Array,
             positions: jax.Array):
    """x (b, s, d), positions (b, s) -> q_nope (b, s, H, nope), roped q_pe
    (b, s, H, rope), normalised c_kv (b, s, 1, r), roped k_pe (b, s, 1,
    rope), in x's dtype."""
    b, s, _ = x.shape
    m, eps = cfg.mla, cfg.rms_norm_eps
    c_q = rms_norm(x @ params["wq_a"], params["q_norm"], eps)
    q = (c_q @ params["wq_b"]).reshape(b, s, cfg.num_heads, m.qk_head_dim)
    kv = x @ params["wkv_a"]
    c_kv = rms_norm(kv[..., :m.kv_lora_rank], params["kv_norm"], eps)
    cos, sin = rope_cos_sin(positions, m.qk_rope_head_dim, cfg.rope_theta,
                            cfg.rope_scaling)
    q_pe = apply_rope(q[..., m.qk_nope_head_dim:], cos, sin)
    k_pe = apply_rope(kv[..., None, m.kv_lora_rank:], cos, sin)
    return q[..., :m.qk_nope_head_dim], q_pe, c_kv[:, :, None], k_pe


def _kv_b(params: Dict, cfg: ModelConfig) -> jax.Array:
    """W_kvb per head: (r, H, nope + v)."""
    m = cfg.mla
    return params["wkv_b"].reshape(m.kv_lora_rank, cfg.num_heads,
                                   m.qk_nope_head_dim + m.v_head_dim)


def _out(params: Dict, cfg: ModelConfig, o: jax.Array, dtype) -> jax.Array:
    """o (b, s, H, v) float32 -> (b, s, d) through W_o."""
    b, s = o.shape[:2]
    # the barrier keeps the residual add and the next norm out of W_o's
    # fusion, which would otherwise end in their untagged ops
    return jax.lax.optimization_barrier(
        o.reshape(b, s, -1).astype(dtype) @ params["wo"])


def _expanded(params: Dict, cfg: ModelConfig, q_nope, q_pe, c_kv, k_pe,
              mask) -> jax.Array:
    """Attention of q (b, s, H, ·) over keys and values up-projected from
    c_kv (b, t, 1, r) and k_pe (b, t, 1, rope), where ``mask`` (b, s, t)
    allows.  Returns (b, s, H, v) float32."""
    nope = cfg.mla.qk_nope_head_dim
    kv = jnp.einsum("btc,chd->bthd", c_kv[:, :, 0], _kv_b(params, cfg),
                    preferred_element_type=F32)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bshd,bthd->bhst", q_nope.astype(F32), k_nope)
              + jnp.einsum("bshr,btr->bhst", q_pe.astype(F32),
                           k_pe[:, :, 0].astype(F32)))
    scores = jnp.where(mask[:, None], scores * softmax_scale(cfg), NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, v)


def _absorbed(params: Dict, cfg: ModelConfig, q_nope, q_pe, c_kv, k_pe,
              mask) -> jax.Array:
    """The same attention with W_kvb absorbed into the query and the
    output: scores against the cached latent itself, where ``mask`` (b,
    s, t) allows.  Returns (b, s, H, v) float32."""
    nope = cfg.mla.qk_nope_head_dim
    w = _kv_b(params, cfg)
    # The einsums' index orders keep each product's last op tagged: in
    # others, XLA ends the fusion in an untagged layout bitcast and the
    # trace does not count its time as ``mla``.
    q_lat = jnp.einsum("bshd,chd->hbsc", q_nope, w[..., :nope],
                       preferred_element_type=F32)
    ckv = c_kv[:, :, 0].astype(F32)
    scores = (jnp.einsum("hbsc,btc->bhst", q_lat, ckv)
              + jnp.einsum("bshr,btr->bhst", q_pe.astype(F32),
                           k_pe[:, :, 0].astype(F32)))
    scores = jnp.where(mask[:, None], scores * softmax_scale(cfg), NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhst,btc->bhsc", p, ckv)
    return jnp.einsum("bhsc,chd->bshd", o_lat, w[..., nope:].astype(F32))


def full_attention(params: Dict, cfg: ModelConfig, x: jax.Array,
                   positions: jax.Array, *, return_kv: bool = False):
    """Causal self-attention over the whole sequence (train / prefill),
    expanded.  ``return_kv`` also returns the cache entries ``(c_kv,
    k_pe)``."""
    with tracing.stage("mla"):
        b, s, _ = x.shape
        if positions.ndim == 1:
            positions = jnp.broadcast_to(positions[None], (b, s))
        q_nope, q_pe, c_kv, k_pe = _project(params, cfg, x, positions)
        mask = positions[:, None, :] <= positions[:, :, None]
        out = _out(params, cfg, _expanded(params, cfg, q_nope, q_pe, c_kv,
                                          k_pe, mask), x.dtype)
        if return_kv:
            return out, (c_kv, k_pe)
        return out


def chunk_attention(params: Dict, cfg: ModelConfig, x: jax.Array, cache,
                    positions: jax.Array):
    """A prompt chunk at ``positions`` (chunk,): its latent entries are
    written into the cache (dense or paged) and its queries attend,
    absorbed, over every cached position up to their own."""
    with tracing.stage("mla"):
        b, s, _ = x.shape
        pos_b = jnp.broadcast_to(positions[None], (b, s))
        q_nope, q_pe, c_kv, k_pe = _project(params, cfg, x, pos_b)
        if isinstance(cache, kvc.PagedKVCache):
            cache = kvc.paged_write_chunk(cache, c_kv, k_pe, positions[0])
            ck, cv = kvc.gather_blocks(cache)
        else:
            cache = kvc.write_chunk(cache, c_kv, k_pe, positions[0])
            ck, cv = cache.k, cache.v
        k_pos = jnp.arange(ck.shape[1], dtype=jnp.int32)
        mask = k_pos[None, None, :] <= pos_b[:, :, None]
        o = _absorbed(params, cfg, q_nope, q_pe, ck, cv, mask)
        return _out(params, cfg, o, x.dtype), cache


def decode_attention(params: Dict, cfg: ModelConfig, x: jax.Array, cache):
    """One token per sequence, x (b, 1, d), against the cache (dense or
    paged), absorbed.  Returns (output (b, 1, d), updated cache)."""
    with tracing.stage("mla"):
        positions = cache.length[:, None]
        q_nope, q_pe, c_kv, k_pe = _project(params, cfg, x, positions)
        if isinstance(cache, kvc.PagedKVCache):
            cache = kvc.paged_append_decode(cache, c_kv, k_pe)
            ck, cv = kvc.gather_blocks(cache)
            valid = kvc.paged_valid_mask(cache)
        else:
            cache = kvc.append_decode(cache, c_kv, k_pe)
            ck, cv = cache.k, cache.v
            valid = kvc.valid_mask(cache)
        o = _absorbed(params, cfg, q_nope, q_pe, ck, cv, valid[:, None])
        return _out(params, cfg, o, x.dtype), cache
