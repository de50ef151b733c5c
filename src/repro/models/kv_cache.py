"""KV caches and recurrent states for serving.

:class:`KVCache` — per-layer (batch, slots, kv_heads, head_dim) buffers with
a per-sequence length counter.  Sliding-window layers allocate only
``window`` slots and write round-robin.  ``window`` is a *static* pytree
field so stacked caches can ride ``lax.scan`` over layers.

:class:`PagedKVCache` — the block-pool alternative: one shared
``(num_blocks, block_size, kv_heads, head_dim)`` pool per layer, with each
sequence naming its blocks through a ``(batch, max_blocks)`` block table
(position ``p`` of sequence ``b`` lives in pool block
``block_tables[b, p // block_size]`` at offset ``p % block_size``).  Block
tables and lengths are *data* — the host-side
:class:`~repro.serving.kv_pool.BlockPool` rewrites them between steps
(admission, prefix-cache sharing, preemption) without recompiling.  Block 0
is reserved as a scratch sink: unset table entries point at it, so writes
from inactive batch rows land somewhere harmless and masked reads of it
contribute exact zeros.

All update ops are functional (return a new cache) so they can live inside
jitted ``serve_step``s and be donated.  The paged view gathered by
:func:`gather_blocks` has width ``max_blocks * block_size``; sized equal to
the dense cache's ``slots``, the paged attention math is lane-for-lane the
dense math, which is what makes dense/paged greedy decode token-identical.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclass
class KVCache:
    """One layer's cache.  k: (batch, slots, kv_heads, head_dim), v the
    same with its own last dim."""

    k: jax.Array
    v: jax.Array
    # number of tokens already written per sequence: (batch,) int32
    length: jax.Array
    # ring buffer (sliding window) if window > 0, else linear — STATIC
    window: int = field(default=0, metadata=dict(static=True))


def init_kv_cache(batch: int, max_seq: int, kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16, window: int = 0,
                  v_head_dim: int = 0) -> KVCache:
    slots = min(window, max_seq) if window else max_seq
    return KVCache(
        k=jnp.zeros((batch, slots, kv_heads, head_dim), dtype),
        v=jnp.zeros((batch, slots, kv_heads, v_head_dim or head_dim), dtype),
        length=jnp.zeros((batch,), jnp.int32),
        window=window,
    )


def kv_cache_spec(batch: int, max_seq: int, kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16, window: int = 0,
                  v_head_dim: int = 0) -> KVCache:
    """ShapeDtypeStruct twin of :func:`init_kv_cache` (for the dry-run)."""
    slots = min(window, max_seq) if window else max_seq
    sds = jax.ShapeDtypeStruct
    return KVCache(
        k=sds((batch, slots, kv_heads, head_dim), dtype),
        v=sds((batch, slots, kv_heads, v_head_dim or head_dim), dtype),
        length=sds((batch,), jnp.int32),
        window=window,
    )


def append_decode(cache: KVCache, k_new: jax.Array, v_new: jax.Array) -> KVCache:
    """Append ONE token per sequence.  k_new/v_new: (batch, 1, kv_heads, hd).

    Implemented as a vmapped dynamic-update-slice (not a gather-scatter):
    GSPMD keeps the batch dim partitioned through DUS, whereas the explicit-
    index scatter forced an all-gather of the cache every layer.
    """
    slots = cache.k.shape[1]
    idx = cache.length % slots if cache.window else cache.length

    def upd(c, new, i):                  # (slots, KV, hd), (KV, hd), scalar
        return jax.lax.dynamic_update_slice_in_dim(c, new[None], i, axis=0)

    k = jax.vmap(upd)(cache.k, k_new[:, 0], idx)
    v = jax.vmap(upd)(cache.v, v_new[:, 0], idx)
    return KVCache(k=k, v=v, length=cache.length + 1, window=cache.window)


def write_prefill(cache: KVCache, k: jax.Array, v: jax.Array) -> KVCache:
    """Write a full prompt (batch, seq, kv_heads, hd) starting at position 0."""
    seq = k.shape[1]
    slots = cache.k.shape[1]
    if cache.window and seq > slots:
        # only the trailing `window` tokens are retained; keep ring phase
        k_tail, v_tail = k[:, -slots:], v[:, -slots:]
        pos = (jnp.arange(seq - slots, seq) % slots)
        ck = cache.k.at[:, pos].set(k_tail)
        cv = cache.v.at[:, pos].set(v_tail)
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k, 0, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v, 0, axis=1)
    length = jnp.full_like(cache.length, seq)
    return KVCache(k=ck, v=cv, length=length, window=cache.window)


def write_chunk(cache: KVCache, k: jax.Array, v: jax.Array,
                start) -> KVCache:
    """Write a prompt *chunk* (batch, chunk, kv_heads, hd) at position
    ``start`` (scalar int32, may be traced).  Linear caches only — chunked
    prefill is gated off for sliding-window layers by the caller."""
    assert cache.window == 0, "write_chunk needs a linear cache"
    seq = k.shape[1]
    ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k, start, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v, start, axis=1)
    length = jnp.full_like(cache.length, start + seq)
    return KVCache(k=ck, v=cv, length=length, window=cache.window)


def valid_mask(cache: KVCache) -> jax.Array:
    """(batch, slots) bool — which cache slots hold valid tokens."""
    slots = cache.k.shape[1]
    pos = jnp.arange(slots)[None, :]
    if cache.window:
        n_valid = jnp.minimum(cache.length, slots)[:, None]
        return pos < jnp.broadcast_to(n_valid, (cache.k.shape[0], slots))
    return pos < cache.length[:, None]


# --------------------------------------------------------------- paged cache

@jax.tree_util.register_dataclass
@dataclass
class PagedKVCache:
    """One layer's block-pool cache.

    k/v: (num_blocks, block_size, kv_heads, head_dim) — shared pool (v
    with its own last dim);
    block_tables: (batch, max_blocks) int32 — per-sequence block names
    (0 = unset, the reserved scratch block);
    length: (batch,) int32 — tokens written per sequence.
    """

    k: jax.Array
    v: jax.Array
    block_tables: jax.Array
    length: jax.Array
    block_size: int = field(default=0, metadata=dict(static=True))


def init_paged_kv_cache(num_blocks: int, block_size: int, batch: int,
                        max_blocks: int, kv_heads: int, head_dim: int,
                        dtype=jnp.bfloat16,
                        v_head_dim: int = 0) -> PagedKVCache:
    vd = v_head_dim or head_dim
    return PagedKVCache(
        k=jnp.zeros((num_blocks, block_size, kv_heads, head_dim), dtype),
        v=jnp.zeros((num_blocks, block_size, kv_heads, vd), dtype),
        block_tables=jnp.zeros((batch, max_blocks), jnp.int32),
        length=jnp.zeros((batch,), jnp.int32),
        block_size=block_size,
    )


def paged_kv_cache_spec(num_blocks: int, block_size: int, batch: int,
                        max_blocks: int, kv_heads: int, head_dim: int,
                        dtype=jnp.bfloat16,
                        v_head_dim: int = 0) -> PagedKVCache:
    """ShapeDtypeStruct twin of :func:`init_paged_kv_cache`."""
    sds = jax.ShapeDtypeStruct
    vd = v_head_dim or head_dim
    return PagedKVCache(
        k=sds((num_blocks, block_size, kv_heads, head_dim), dtype),
        v=sds((num_blocks, block_size, kv_heads, vd), dtype),
        block_tables=sds((batch, max_blocks), jnp.int32),
        length=sds((batch,), jnp.int32),
        block_size=block_size,
    )


def _lookup_blocks(cache: PagedKVCache, positions: jax.Array) -> jax.Array:
    """Map per-row positions (batch, n) to pool block ids via the table.

    Positions at or past capacity clamp to the last table entry — the same
    "write the final slot" behaviour the dense cache's dynamic-update-slice
    shows at capacity (the engine retires such requests right after)."""
    bi = jnp.clip(positions // cache.block_size, 0,
                  cache.block_tables.shape[1] - 1)
    return jnp.take_along_axis(cache.block_tables, bi, axis=1)


def paged_append_decode(cache: PagedKVCache, k_new: jax.Array,
                        v_new: jax.Array) -> PagedKVCache:
    """Append ONE token per sequence.  k_new/v_new: (batch, 1, kv_heads, hd).

    The tail block of every *live* sequence is private (the block-pool
    invariant), so the batched scatter has no cross-row aliasing; inactive
    rows (length 0, table all-unset) write the scratch block, which is never
    read.
    """
    blocks = _lookup_blocks(cache, cache.length[:, None])[:, 0]   # (batch,)
    off = cache.length % cache.block_size
    k = cache.k.at[blocks, off].set(k_new[:, 0])
    v = cache.v.at[blocks, off].set(v_new[:, 0])
    return PagedKVCache(k=k, v=v, block_tables=cache.block_tables,
                        length=cache.length + 1,
                        block_size=cache.block_size)


def paged_write_chunk(cache: PagedKVCache, k: jax.Array, v: jax.Array,
                      start) -> PagedKVCache:
    """Write a prompt chunk (batch, chunk, kv_heads, hd) at position
    ``start`` (scalar int32, may be traced) through the block table."""
    B, C = k.shape[0], k.shape[1]
    pos = start + jnp.arange(C, dtype=jnp.int32)
    blocks = _lookup_blocks(cache, jnp.broadcast_to(pos[None], (B, C)))
    off = jnp.broadcast_to((pos % cache.block_size)[None], (B, C))
    ck = cache.k.at[blocks, off].set(k)
    cv = cache.v.at[blocks, off].set(v)
    length = jnp.full_like(cache.length, start + C)
    return PagedKVCache(k=ck, v=cv, block_tables=cache.block_tables,
                        length=length, block_size=cache.block_size)


def gather_blocks(cache: PagedKVCache):
    """Materialize the per-sequence view: 2× (batch, max_blocks·bs, KV, hd).

    View lane ``j`` of row ``b`` holds position ``j`` — identical layout to
    a dense :class:`KVCache` of ``max_blocks * block_size`` slots, so the
    downstream attention math is shared verbatim."""
    B, mb = cache.block_tables.shape
    bs, kvh = cache.k.shape[1], cache.k.shape[2]
    kv = cache.k[cache.block_tables].reshape(B, mb * bs, kvh, -1)
    vv = cache.v[cache.block_tables].reshape(B, mb * bs, kvh, -1)
    return kv, vv


def paged_valid_mask(cache: PagedKVCache) -> jax.Array:
    """(batch, max_blocks·bs) bool over the gathered view."""
    slots = cache.block_tables.shape[1] * cache.block_size
    pos = jnp.arange(slots)[None, :]
    return pos < cache.length[:, None]


def copy_blocks(cache: PagedKVCache, src: jax.Array, dst: jax.Array, *,
                stacked: bool = False) -> PagedKVCache:
    """Copy pool blocks ``src[i] -> dst[i]`` (copy-on-write forks).

    ``stacked`` handles the scan-over-layers layout where every leaf
    carries a leading layer dim (blocks at axis 1 instead of 0)."""
    if stacked:
        k = cache.k.at[:, dst].set(cache.k[:, src])
        v = cache.v.at[:, dst].set(cache.v[:, src])
    else:
        k = cache.k.at[dst].set(cache.k[src])
        v = cache.v.at[dst].set(cache.v[src])
    return PagedKVCache(k=k, v=v, block_tables=cache.block_tables,
                        length=cache.length, block_size=cache.block_size)
