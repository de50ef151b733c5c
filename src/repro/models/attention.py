"""GQA attention: training/prefill (full, causal, optional sliding window),
decode against a KV cache, and cross-attention (whisper decoder).

Pure per-shard math; distribution (TP over heads, DP over batch, SP over the
cache for long contexts) is applied by the launch layer via shardings.
RoPE is applied to q/k *before* the keys are cached, so cached keys are
already rotated (standard practice; makes ring-buffer windows trivial).
What the public functions here trace (projections, rope, the cache write,
the attention itself and the output projection) carries the ``attention``
stage tag (:func:`repro.tracing.stage`).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.configs.base import ModelConfig
from repro.models import kv_cache as kvc
from repro.models.common import dense_init, softcap
from repro.models.rope import apply_rope, mrope_cos_sin, rope_cos_sin, text_mrope_positions

NEG_INF = -1e30


# --------------------------------------------------------------------- init

def init_attention(key, cfg: ModelConfig, cross: bool = False) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * hd, dt),
        "wk": dense_init(ks[1], d, kv * hd, dt),
        "wv": dense_init(ks[2], d, kv * hd, dt),
        "wo": dense_init(ks[3], h * hd, d, dt),
    }


# ------------------------------------------------------------------ helpers

def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    return x.reshape(x.shape[:-1] + (n, hd))


def _rope_for(cfg: ModelConfig, positions: jax.Array, mrope_positions=None):
    if cfg.mrope_sections is not None:
        pos3 = (mrope_positions if mrope_positions is not None
                else text_mrope_positions(positions))
        return mrope_cos_sin(pos3, cfg.head_dim, cfg.rope_theta,
                             cfg.mrope_sections)
    return rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (b, sq, H, hd), k: (b, sk, KV, hd) -> (b, KV, G, sq, sk) fp32."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q = q.reshape(b, sq, kvh, g, hd)
    return jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                      k.astype(jnp.float32))


def _gqa_out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p: (b, KV, G, sq, sk), v: (b, sk, KV, hd) -> (b, sq, H, hd)."""
    b, kvh, g, sq, sk = p.shape
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return out.reshape(b, sq, kvh * g, v.shape[-1])


# ------------------------------------------------------- full (train/prefill)

# sequences at or above this length use the q-block-chunked (flash-style)
# path so (S, S) score matrices never materialize
CHUNKED_ATTN_THRESHOLD = 2048


def full_attention(params: Dict, cfg: ModelConfig, x: jax.Array,
                   positions: jax.Array, *, is_local: bool = False,
                   mrope_positions=None, causal: bool = True,
                   return_kv: bool = False, q_blocks: int = 16,
                   unroll: bool = False):
    """Self-attention over the full sequence (train / prefill).

    x: (batch, seq, d_model); positions: (batch, seq) or (seq,) int32.
    ``is_local`` applies cfg.sliding_window masking (gemma3 local layers);
    ``causal=False`` gives the bidirectional encoder variant (whisper);
    ``return_kv`` additionally returns the rotated (k, v) for cache fills.
    Long sequences run the chunked path: a scan over q blocks, each block
    rematerialized in backward (flash-attention memory profile).
    """
    with tracing.stage("attention"):
        b, s, d = x.shape
        h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _split_heads(x @ params["wq"], h, hd)
        k = _split_heads(x @ params["wk"], kvh, hd)
        v = _split_heads(x @ params["wv"], kvh, hd)

        if positions.ndim == 1:
            positions = jnp.broadcast_to(positions[None], (b, s))
        cos, sin = _rope_for(cfg, positions, mrope_positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        if s >= CHUNKED_ATTN_THRESHOLD and s % q_blocks == 0:
            out = _chunked_core(cfg, q, k, v, positions,
                                is_local=is_local, causal=causal,
                                q_blocks=q_blocks, unroll=unroll)
        else:
            out = _dense_core(cfg, q, k, v, positions, positions,
                              is_local=is_local, causal=causal)
        out = out.reshape(b, s, h * hd).astype(x.dtype) @ params["wo"]
        if return_kv:
            return out, (k, v)
        return out


def _dense_core(cfg, q, k, v, q_pos, k_pos, *, is_local, causal):
    """Reference path: materialized scores.  Returns (b, sq, H, hd) fp32."""
    hd = cfg.head_dim
    scores = _gqa_scores(q, k) / math.sqrt(hd)
    if cfg.attn_logit_softcap:
        scores = softcap(scores, cfg.attn_logit_softcap)
    i = q_pos[:, None, None, :, None]
    j = k_pos[:, None, None, None, :]
    mask = (j <= i) if causal else jnp.broadcast_to(
        jnp.bool_(True), (j <= i).shape)
    if is_local and cfg.sliding_window:
        mask &= (i - j) < cfg.sliding_window
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(p, v)


def _chunked_core(cfg, q, k, v, positions, *, is_local, causal,
                  q_blocks: int, unroll: bool):
    """Flash-style: scan over q blocks against full K/V; each block body is
    checkpointed so backward recomputes its scores instead of saving them."""
    b, s, h, hd = q.shape
    bq = s // q_blocks
    qb = q.reshape(b, q_blocks, bq, h, hd).swapaxes(0, 1)     # (nq,b,bq,h,hd)
    pb = positions.reshape(b, q_blocks, bq).swapaxes(0, 1)

    def body(_, inp):
        qi, pi = inp
        out = _dense_core(cfg, qi, k, v, pi, positions,
                          is_local=is_local, causal=causal)
        return None, out

    _, outs = jax.lax.scan(jax.checkpoint(body), None, (qb, pb),
                           unroll=unroll)
    return outs.swapaxes(0, 1).reshape(b, s, h, hd)


# ------------------------------------------------------------ chunk prefill

def chunk_attention(params: Dict, cfg: ModelConfig, x: jax.Array,
                    cache, positions: jax.Array, *,
                    mrope_positions=None):
    """Prefill one prompt *chunk* against the cache (chunked prefill).

    x: (batch, chunk, d_model); positions: (chunk,) global token positions
    (``start + arange(chunk)``).  The chunk's rotated K/V are written into
    the cache at ``positions[0]`` and the chunk's queries attend over every
    cached position ``<=`` their own — earlier chunks included — so the
    result matches a single full-prompt prefill (slots beyond the causal
    frontier are masked; masked lanes contribute exact zeros).

    ``cache`` may be a dense :class:`~repro.models.kv_cache.KVCache` or a
    :class:`~repro.models.kv_cache.PagedKVCache`; the paged branch writes
    through the block table and attends over the gathered view — including
    prefix-cache blocks written by an *earlier* request, which is how a
    prefix hit lets the chunk start mid-prompt.
    """
    with tracing.stage("attention"):
        b, s, d = x.shape
        h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _split_heads(x @ params["wq"], h, hd)
        k = _split_heads(x @ params["wk"], kvh, hd)
        v = _split_heads(x @ params["wv"], kvh, hd)

        pos_b = jnp.broadcast_to(positions[None], (b, s))
        cos, sin = _rope_for(cfg, pos_b, mrope_positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        if isinstance(cache, kvc.PagedKVCache):
            cache = kvc.paged_write_chunk(cache, k, v, positions[0])
            ck, cv = kvc.gather_blocks(cache)
        else:
            cache = kvc.write_chunk(cache, k, v, positions[0])
            ck, cv = cache.k, cache.v
        slots = ck.shape[1]
        k_pos = jnp.broadcast_to(jnp.arange(slots, dtype=jnp.int32)[None],
                                 (b, slots))
        out = _dense_core(cfg, q, ck, cv, pos_b, k_pos,
                          is_local=False, causal=True)
        out = out.reshape(b, s, h * hd).astype(x.dtype) @ params["wo"]
        return out, cache


# ------------------------------------------------------------------- decode

def decode_attention(params: Dict, cfg: ModelConfig, x: jax.Array,
                     cache, *, is_local: bool = False,
                     mrope_positions=None):
    """One-token decode: x (batch, 1, d_model) against the cache.

    ``cache`` may be dense or paged (:class:`~repro.models.kv_cache
    .PagedKVCache`); the paged branch appends through the block table and
    attends over the gathered view — lane-for-lane the dense math when the
    view width equals the dense slot count, so greedy outputs match
    bitwise.  (Sliding-window ``is_local`` layers are dense-only; the
    serving engine pages the uniform decoder family.)

    Returns (output (batch, 1, d_model), updated cache).
    """
    with tracing.stage("attention"):
        b = x.shape[0]
        h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _split_heads(x @ params["wq"], h, hd)        # (b,1,H,hd)
        k = _split_heads(x @ params["wk"], kvh, hd)
        v = _split_heads(x @ params["wv"], kvh, hd)

        positions = cache.length[:, None]            # (b,1) current position
        cos, sin = _rope_for(cfg, positions, mrope_positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        if isinstance(cache, kvc.PagedKVCache):
            cache = kvc.paged_append_decode(cache, k, v)
            ck, cv = kvc.gather_blocks(cache)
            mask = kvc.paged_valid_mask(cache)[:, None, None, None, :]
        else:
            cache = kvc.append_decode(cache, k, v)
            ck, cv = cache.k, cache.v
            mask = kvc.valid_mask(cache)[:, None, None, None, :]
        scores = _gqa_scores(q, ck) / math.sqrt(hd)        # (b,KV,G,1,slots)
        if cfg.attn_logit_softcap:
            scores = softcap(scores, cfg.attn_logit_softcap)
        scores = jnp.where(mask, scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(p, cv).astype(x.dtype)
        return out.reshape(b, 1, h * hd) @ params["wo"], cache


def decode_attention_partial(params: Dict, cfg: ModelConfig, q: jax.Array,
                             cache: kvc.KVCache
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash-decode partial pass over a cache *shard* (sequence parallelism).

    q: (b, 1, H, hd) already rotated.  Returns (acc, max, lse) so shards can
    be combined with a small cross-shard softmax reduction:
        acc: (b, 1, H, hd) unnormalized sum of p*v, m: (b,1,H,1), l: (b,1,H,1)
    """
    with tracing.stage("attention"):
        hd = cfg.head_dim
        scores = _gqa_scores(q, cache.k) / math.sqrt(hd)   # (b,KV,G,1,slots)
        mask = kvc.valid_mask(cache)[:, None, None, None, :]
        scores = jnp.where(mask, scores, NEG_INF)
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = _gqa_out(p, cache.v)                         # (b,1,H,hd) fp32
        b, _, h, _ = q.shape
        m = m.reshape(b, 1, h, 1)
        l = l.reshape(b, 1, h, 1)
        return acc, m, l


def combine_partial_attention(acc, m, l, axis_name: str):
    """Combine flash-decode partials across a shard_map axis."""
    g_m = jax.lax.pmax(m, axis_name)
    scale = jnp.exp(m - g_m)
    num = jax.lax.psum(acc * scale, axis_name)
    den = jax.lax.psum(l * scale, axis_name)
    return num / jnp.maximum(den, 1e-30)


# ------------------------------------------------------------- cross-attn

def init_cross_attention(key, cfg: ModelConfig) -> Dict:
    return init_attention(key, cfg, cross=True)


def cross_attention(params: Dict, cfg: ModelConfig, x: jax.Array,
                    enc_out: jax.Array) -> jax.Array:
    """Decoder cross-attention (whisper): queries from x, k/v from enc_out."""
    with tracing.stage("attention"):
        b, s, d = x.shape
        h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        k = _split_heads(enc_out @ params["wk"], kvh, hd)
        v = _split_heads(enc_out @ params["wv"], kvh, hd)
        return cross_attention_cached(params, cfg, x, k, v)


def cross_attention_cached(params: Dict, cfg: ModelConfig, x: jax.Array,
                           k: jax.Array, v: jax.Array) -> jax.Array:
    """Cross-attention against precomputed encoder K/V (decode path)."""
    with tracing.stage("attention"):
        b, s, d = x.shape
        h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _split_heads(x @ params["wq"], h, hd)
        scores = _gqa_scores(q, k) / math.sqrt(hd)
        p = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(p, v).astype(x.dtype)
        return out.reshape(b, s, h * hd) @ params["wo"]
