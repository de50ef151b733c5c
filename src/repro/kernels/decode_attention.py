"""Pallas TPU flash-decode kernels: one-token GQA attention over a KV cache.

The attention client's hot loop during decoding.  Online-softmax over KV
blocks; grid = (batch, seq_blocks) with the sequence dimension innermost so
the (KV, G, hd) accumulator lives in VMEM scratch across blocks.  A block
holds every KV head, ``(1, ts, KV, hd)``: the TPU tiles the last two dims of
a block, and a single-head block ``(1, ts, 1, hd)`` would leave a
second-minor dim of 1 that is neither a multiple of 8 nor the full KV, which
Mosaic refuses.  The kernel body loops over the KV heads statically.
Sequence lengths arrive via scalar prefetch; padded cache slots are masked.

Two variants share the kernel body:

* :func:`flash_decode_pallas` — dense per-sequence cache
  ``(B, S, KV, hd)``; KV block ``s`` of sequence ``b`` is just the
  contiguous slice at ``s``.
* :func:`paged_flash_decode_pallas` — block-pool cache: all sequences share
  one pool ``(num_blocks, bs, KV, hd)`` and each sequence names its blocks
  through a ``(B, max_blocks)`` block table.  The table rides scalar
  prefetch, so the *index map* gathers: grid step ``(b, s)`` DMAs pool
  block ``tables[b, s]`` — the kernel body never sees the indirection.

VMEM per step: 2·TS·KV·hd (k, v) + H·hd (q) + H·hd·4 (acc) — for TS=512,
KV=8, hd=64, H=128 in bf16: ~1.2 MiB before double buffering (the lane dim
pads hd 64 to 128).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _kernel(lengths, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, ts: int, n_s: int, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    span = s * ts + jax.lax.broadcasted_iota(jnp.int32, (1, ts), 1)
    valid = span < lengths[b]                            # (1, TS)

    for h in range(q_ref.shape[1]):                      # KV heads, static
        q = q_ref[0, h].astype(jnp.float32)              # (G, hd)
        k = k_ref[0, :, h, :].astype(jnp.float32)        # (TS, hd)
        v = v_ref[0, :, h, :].astype(jnp.float32)        # (TS, hd)

        scores = jax.lax.dot_general(                    # (G, TS)
            q, k, (((1,), (1,)), ((), ()))) * scale
        scores = jnp.where(valid, scores, NEG)

        m_prev = m_ref[h]                                # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        # explicit mask: a fully-invalid block must contribute nothing
        p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)                  # (G, 1)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + p @ v
        m_ref[h] = m_new

    @pl.when(s == n_s - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def _scratch(KV: int, G: int, hd: int):
    return [pltpu.VMEM((KV, G, hd), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32)]


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def flash_decode_pallas(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                        lengths: jax.Array, *, ts: int = 512,
                        interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); k/v_cache: (B, S, KV, hd); lengths: (B,) >= 1.

    Returns (B, H, hd).  S must be a multiple of ts.
    """
    B, H, hd = q.shape
    _, S, KV, _ = k_cache.shape
    G = H // KV
    assert G * KV == H and S % ts == 0, (H, KV, S, ts)
    qg = q.reshape(B, KV, G, hd)

    n_s = S // ts
    kernel = functools.partial(_kernel, ts=ts, n_s=n_s,
                               scale=1.0 / np.sqrt(hd))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_s),
            in_specs=[
                pl.BlockSpec((1, KV, G, hd), lambda b, s, L: (b, 0, 0, 0)),
                pl.BlockSpec((1, ts, KV, hd), lambda b, s, L: (b, s, 0, 0)),
                pl.BlockSpec((1, ts, KV, hd), lambda b, s, L: (b, s, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, KV, G, hd),
                                   lambda b, s, L: (b, 0, 0, 0)),
            scratch_shapes=_scratch(KV, G, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(B, H, hd)


def _paged_kernel(tables, lengths, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, ts: int, n_s: int, scale: float):
    # the block table is consumed by the index maps; the body is the dense
    # online-softmax kernel (view lane j of sequence b == position j)
    _kernel(lengths, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            ts=ts, n_s=n_s, scale=scale)


def paged_flash_decode_pallas(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, block_tables: jax.Array,
                              lengths: jax.Array, *,
                              interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); k/v_pool: (num_blocks, bs, KV, hd);
    block_tables: (B, max_blocks) int32; lengths: (B,) >= 1.

    Sequence ``b``'s position ``p`` lives in pool block
    ``block_tables[b, p // bs]`` at offset ``p % bs``; positions at or past
    ``lengths[b]`` are masked.  Returns (B, H, hd) — numerically the dense
    :func:`flash_decode_pallas` over the gathered view.
    """
    B, H, hd = q.shape
    _, bs, KV, _ = k_pool.shape
    _, n_s = block_tables.shape
    G = H // KV
    assert G * KV == H, (H, KV)
    qg = q.reshape(B, KV, G, hd)

    kernel = functools.partial(_paged_kernel, ts=bs, n_s=n_s,
                               scale=1.0 / np.sqrt(hd))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_s),
            in_specs=[
                pl.BlockSpec((1, KV, G, hd), lambda b, s, T, L: (b, 0, 0, 0)),
                # the paged gather: block s of sequence b is pool block
                # T[b, s] — the DMA indirection lives in the index map
                pl.BlockSpec((1, bs, KV, hd),
                             lambda b, s, T, L: (T[b, s], 0, 0, 0)),
                pl.BlockSpec((1, bs, KV, hd),
                             lambda b, s, T, L: (T[b, s], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, KV, G, hd),
                                   lambda b, s, T, L: (b, 0, 0, 0)),
            scratch_shapes=_scratch(KV, G, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pool, v_pool)
    return out.reshape(B, H, hd)
