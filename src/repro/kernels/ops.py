"""Jit-ready wrappers around the Pallas kernels with CPU-friendly lowerings.

Every op has several implementations:

* ``pallas`` / ``pallas_interpret`` — the TPU kernel (interpret=True runs the
  kernel body in Python on CPU; used by the allclose tests).
* ``ref`` — the oracle from :mod:`repro.kernels.ref`.

The grouped GEMM has two more:

* ``xla_ragged`` — :func:`grouped_gemm_masked`, a batched matmul over the
  groups on masked rows: exact, and a row's result is the same wherever its
  group sits in the bank, so moving an expert to another server or slot
  never changes its outputs.  Its flop count over-counts by G×, so it is
  NOT used for the roofline dry-run.
* ``xla_dense`` — per-expert-capacity batched matmul (GShard-style): the
  flop-honest XLA lowering used by the dry-run; FLOPs = 2·L·cap·d·f which at
  the configured capacity factor equals the ideal grouped-GEMM work.  Rows
  past an expert's capacity are dropped, so it is not exact under skew.

A grouped GEMM may be given a ``group_offset``: group ``g`` then multiplies
by ``w[group_offset + g]``, so one call reads its groups out of a larger
bank (every layer's expert servers stacked).  The Pallas kernel adds the
offset to its tile table and reads the bank in place; the XLA lowerings
slice the call's groups out with ``lax.dynamic_slice_in_dim``.

``impl="auto"`` picks from the platform: ``pallas`` on TPU; on the CPU
``xla_ragged`` for the grouped GEMM and ``ref`` (the plain XLA lowering)
for the others.  An op given an implementation it does not have raises.
``set_default_impl`` lets the launch layer pick one globally (the dry-run
sets ``xla_dense``; tests pin impls explicitly).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.kernels import ref as kref
from repro.kernels.combine import combine_weighted_pallas
from repro.kernels.decode_attention import (flash_decode_pallas,
                                            paged_flash_decode_pallas)
from repro.kernels.grouped_gemm import RowTiles, grouped_gemm_pallas

_DEFAULT_IMPL: Optional[str] = None


def set_default_impl(impl: Optional[str]) -> None:
    global _DEFAULT_IMPL
    _DEFAULT_IMPL = impl


_KERNEL_IMPLS = ("ref", "pallas", "pallas_interpret")
_GEMM_IMPLS = _KERNEL_IMPLS + ("xla_ragged", "xla_dense")


def _resolve(op: str, impl: str, cpu_impl: str, have) -> str:
    if impl == "auto":
        if _DEFAULT_IMPL is not None:
            impl = _DEFAULT_IMPL
        else:
            impl = "pallas" if jax.default_backend() == "tpu" else cpu_impl
    if impl not in have:
        raise ValueError(f"{op} has no implementation {impl!r}; "
                         f"expected one of {have} or 'auto'")
    return impl


# --------------------------------------------------------------- grouped gemm

def grouped_gemm_dense(x_sorted: jax.Array, w: jax.Array,
                       group_sizes: jax.Array, capacity: int) -> jax.Array:
    """GShard-style per-expert-capacity batched matmul.

    Scatters the group-sorted rows into (G, capacity, K), one batched matmul
    per weight, gathers back.  Rows beyond an expert's capacity are dropped
    (the launch layer sizes ``capacity`` from the dispatch capacity factor so
    this only triggers under extreme imbalance).
    """
    M, K = x_sorted.shape
    G, _, N = w.shape
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes).astype(jnp.int32)])
    rows = jnp.arange(M, dtype=jnp.int32)
    gid = jnp.searchsorted(offsets[1:], rows, side="right").astype(jnp.int32)
    live = rows < offsets[-1]
    gid_c = jnp.minimum(gid, G - 1)
    pos = rows - offsets[gid_c]
    ok = live & (pos < capacity)
    idx = jnp.where(ok, gid_c * capacity + pos, G * capacity)
    xg = jnp.zeros((G * capacity, K), x_sorted.dtype).at[idx].set(
        x_sorted, mode="drop").reshape(G, capacity, K)
    yg = jnp.einsum("gck,gkn->gcn", xg, w,
                    preferred_element_type=jnp.float32)
    y = yg.reshape(G * capacity, N)
    safe = jnp.minimum(idx, G * capacity - 1)
    out = jnp.where(ok[:, None], y[safe], 0)
    return out.astype(x_sorted.dtype)


def grouped_gemm_masked(x_sorted: jax.Array, w: jax.Array,
                        group_sizes: jax.Array) -> jax.Array:
    """Exact grouped matmul: one batched matmul over the groups, each on
    the rows of its own group (the others masked to zero), summed over the
    groups.  A row meets only its group's K products, so its result does
    not depend on where its group sits in the bank or how many groups
    there are; ``jax.lax.ragged_dot`` on the CPU contracts over all G·K
    products at once, and its sums then move in the last bits when the
    groups do.  Rows past ``sum(group_sizes)`` yield zeros."""
    G = w.shape[0]
    gid = jnp.searchsorted(jnp.cumsum(group_sizes),
                           jnp.arange(x_sorted.shape[0]), side="right")
    mine = gid[None, :, None] == jnp.arange(G)[:, None, None]
    y = jnp.einsum("gmk,gkn->gmn", jnp.where(mine, x_sorted[None], 0), w,
                   preferred_element_type=jnp.float32)
    return jnp.sum(y, axis=0).astype(x_sorted.dtype)


def grouped_gemm(x_sorted: jax.Array, w: jax.Array, group_sizes: jax.Array,
                 *, impl: str = "auto", expert_capacity: Optional[int] = None,
                 group_offset=0, tm: int = 128, tn: Optional[int] = None,
                 tk: Optional[int] = None) -> jax.Array:
    """out[i] = x_sorted[i] @ w[group_offset + g(i)] — see module docstring
    for impls.  The XLA lowerings are traced in the ``gemm`` stage; the
    Pallas kernel carries that tag in its own metadata, and takes the
    blocks not given from the shapes
    (:func:`repro.kernels.grouped_gemm.choose_blocks`)."""
    impl = _resolve("grouped_gemm", impl, "xla_ragged", _GEMM_IMPLS)
    if impl in ("pallas", "pallas_interpret"):
        return grouped_gemm_pallas(
            x_sorted, w, group_sizes, group_offset=group_offset, tm=tm,
            tn=tn, tk=tk, interpret=(impl == "pallas_interpret"))[0]
    with tracing.stage("gemm"):
        w = jax.lax.dynamic_slice_in_dim(w, group_offset,
                                         group_sizes.shape[0])
        if impl == "ref":
            return kref.grouped_gemm_ref(x_sorted, w, group_sizes)
        if impl == "xla_ragged":
            return grouped_gemm_masked(x_sorted, w, group_sizes)
        M, G = x_sorted.shape[0], w.shape[0]
        cap = expert_capacity or max(_ceil_mult(2 * M // max(G, 1) + 1, 8), 8)
        return grouped_gemm_dense(x_sorted, w, group_sizes, cap)


def grouped_gemm_counted(x_sorted: jax.Array, w: jax.Array,
                         group_sizes: jax.Array, *, impl: str = "auto",
                         expert_capacity: Optional[int] = None,
                         group_offset=0) -> Tuple[jax.Array, RowTiles]:
    """:func:`grouped_gemm` and the Pallas kernel's row tiles, live and
    laid out; the XLA lowerings have none and count zero."""
    impl = _resolve("grouped_gemm", impl, "xla_ragged", _GEMM_IMPLS)
    if impl in ("pallas", "pallas_interpret"):
        return grouped_gemm_pallas(x_sorted, w, group_sizes,
                                   group_offset=group_offset,
                                   interpret=(impl == "pallas_interpret"))
    zero = jnp.zeros((), jnp.int32)
    return (grouped_gemm(x_sorted, w, group_sizes, impl=impl,
                         expert_capacity=expert_capacity,
                         group_offset=group_offset),
            RowTiles(zero, zero))


def _ceil_mult(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# --------------------------------------------------------------- flash decode

def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, *, impl: str = "auto",
                 ts: int = 512) -> jax.Array:
    impl = _resolve("flash_decode", impl, "ref", _KERNEL_IMPLS)
    if impl == "ref":
        return kref.flash_decode_ref(q, k_cache, v_cache, lengths)
    return flash_decode_pallas(q, k_cache, v_cache, lengths, ts=ts,
                               interpret=(impl == "pallas_interpret"))


def paged_flash_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       block_tables: jax.Array, lengths: jax.Array, *,
                       impl: str = "auto") -> jax.Array:
    """Flash decode over a shared block pool gathered through block tables."""
    impl = _resolve("paged_flash_decode", impl, "ref", _KERNEL_IMPLS)
    if impl == "ref":
        return kref.paged_flash_decode_ref(q, k_pool, v_pool, block_tables,
                                           lengths)
    return paged_flash_decode_pallas(
        q, k_pool, v_pool, block_tables, lengths,
        interpret=(impl == "pallas_interpret"))


# -------------------------------------------------------------------- combine

def combine_weighted(x: jax.Array, w: jax.Array, *, impl: str = "auto",
                     tt: int = 128, td: int = 512) -> jax.Array:
    impl = _resolve("combine_weighted", impl, "ref", _KERNEL_IMPLS)
    if impl == "ref":
        return kref.combine_weighted_ref(x, w)
    return combine_weighted_pallas(x, w, tt=tt, td=td,
                                   interpret=(impl == "pallas_interpret"))
