"""Pallas TPU grouped GEMM with group-shrink (the paper's expert-server
kernel, §4.1, adapted per DESIGN.md §6).

Computes ``out[i] = x[i] @ w[g(i)]`` for rows sorted by group, where the
tile→group mapping comes from :mod:`repro.kernels.group_shrink` through
scalar prefetch (SMEM).  Grid = (row_tiles, N tiles, K tiles); inactive
groups occupy zero row tiles, dead tail tiles skip the MXU via ``pl.when``.

The kernel streams the weight bank from HBM, one (tk, tn) block a grid
step, so its time is bytes over bandwidth once a step moves enough bytes
to hide the step's fixed cost (~0.3 µs on v5e, against 0.04 µs for a
128×128 bf16 block).  Unless the caller gives them, :func:`choose_blocks`
takes tk and tn from K and N: the largest blocks, power-of-two multiples of
128 dividing the dimension, whose double-buffered working set (x block and
weight block, plus the f32 output block) fits :data:`VMEM_BUDGET` (larger
blocks gained under 1% on v5e); of those, the one that reads each row
tile's x rows the fewest times, since XLA may leave x in HBM, where every
read competes with the weights.  At Mixtral's 4096×14336 that is a
4096×512 weight block of 4 MiB, whose x block stays put over the tile's N
blocks.  The scoped VMEM limit is raised to the working set when it passes
the default.

Dead row tiles fetch nothing.  The pipeline skips a block's copy when its
index equals the previous grid step's, so a dead tile's weight block is
the last live tile's last block (the tile table gives dead tiles the last
live group), and its x and output blocks are one fixed block, fetched and
written back once.  Dead tiles' output rows are left unwritten; only live
rows are read back.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import tracing
from repro.kernels import group_shrink as gs

# the double-buffered working set :func:`choose_blocks` fits; v5e has far
# more VMEM than the scoped default the compiler gives a kernel
VMEM_BUDGET = 12 * 2 ** 20
SCOPED_VMEM_DEFAULT = 16 * 2 ** 20
VMEM_HEADROOM = 4 * 2 ** 20     # the compiler's own scratch beside the blocks
_LANE = 128


class RowTiles(NamedTuple):
    """Row tiles of one grouped GEMM: live ones ran, all ones were laid out."""

    live: jax.Array    # scalar int32
    total: jax.Array   # scalar int32


def working_set(tm: int, tk: int, tn: int, x_bytes: int = 2,
                w_bytes: int = 2) -> int:
    """VMEM bytes of the double-buffered x, weight and f32 output blocks."""
    return 2 * (tm * tk * x_bytes + tk * tn * w_bytes + tm * tn * 4)


def _block_sizes(dim: int) -> Tuple[int, ...]:
    """Power-of-two multiples of 128 dividing ``dim``; else the whole dim."""
    sizes = tuple(b for b in (_LANE << s for s in range(dim.bit_length()))
                  if b <= dim and dim % b == 0)
    return sizes or (dim,)


def x_reads(k: int, n: int, tk: int, tn: int) -> int:
    """How often a live row tile reads its x rows: once for each N block,
    since the K blocks run innermost, unless one K block spans K and the
    x block stays put."""
    return 1 if tk == k else n // tn


def choose_blocks(k: int, n: int, *, tm: int = 128, x_bytes: int = 2,
                  w_bytes: int = 2, budget: int = VMEM_BUDGET
                  ) -> Tuple[int, int]:
    """(tk, tn): the largest weight block whose working set fits ``budget``;
    of those, the one that reads each row tile's x the fewest times
    (:func:`x_reads`), then the one with the smallest working set; the
    smallest blocks when none fits."""
    best, score = (_block_sizes(k)[0], _block_sizes(n)[0]), None
    for tk in _block_sizes(k):
        for tn in _block_sizes(n):
            ws = working_set(tm, tk, tn, x_bytes, w_bytes)
            key = (tk * tn, -x_reads(k, n, tk, tn), -ws)
            if ws <= budget and (score is None or key > score):
                best, score = (tk, tn), key
    return best


def _kernel(tile_gid, tile_valid, x_ref, w_ref, o_ref):
    i, k = pl.program_id(0), pl.program_id(2)
    live = tile_valid[i] > 0

    @pl.when(live & (k == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _compute():
        o_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32)


def grouped_gemm_pallas(x_sorted: jax.Array, w: jax.Array,
                        group_sizes: jax.Array, *,
                        group_offset=0,
                        tm: int = 128, tn: Optional[int] = None,
                        tk: Optional[int] = None,
                        interpret: bool = False
                        ) -> Tuple[jax.Array, RowTiles]:
    """x_sorted: (M, K) rows sorted by group; w: (W, K, N); group_sizes:
    (G,); -> ((M, N), the row tiles).

    Group ``g`` multiplies by ``w[group_offset + g]``: the offset (a
    scalar, traced or not) goes into the tile table the kernel prefetches,
    so a bank holding more groups than the call (a stacked layer axis
    folded into W) is read in place, never sliced.  Rows beyond
    ``sum(group_sizes)`` yield zeros.  K and N must be multiples of tk/tn;
    blocks not given are chosen from the shapes (:func:`choose_blocks`).
    """
    M, K = x_sorted.shape
    _, K2, N = w.shape
    assert K == K2, (K, K2)
    auto_tk, auto_tn = choose_blocks(
        K, N, tm=tm, x_bytes=x_sorted.dtype.itemsize,
        w_bytes=w.dtype.itemsize)
    tk, tn = tk or auto_tk, tn or auto_tn
    assert K % tk == 0 and N % tn == 0, (K, N, tk, tn)

    table = gs.build_tile_table(group_sizes, M, tm)
    x_pad, padded_idx, row_live = gs.pad_rows_to_tiles(
        x_sorted, group_sizes, table, tm)
    T = table.tile_gid.shape[0]
    n_j, n_k = N // tn, K // tk

    # a dead tile's blocks repeat what the grid step before it holds
    def x_map(i, j, k, gid, vld):
        live = vld[i] > 0
        return jnp.where(live, i, T - 1), jnp.where(live, k, n_k - 1)

    def w_map(i, j, k, gid, vld):
        live = vld[i] > 0
        return (gid[i], jnp.where(live, k, n_k - 1),
                jnp.where(live, j, n_j - 1))

    def o_map(i, j, k, gid, vld):
        live = vld[i] > 0
        return jnp.where(live, i, T - 1), jnp.where(live, j, n_j - 1)

    vmem = VMEM_HEADROOM + working_set(tm, tk, tn, x_sorted.dtype.itemsize,
                                       w.dtype.itemsize)
    out_pad = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T, n_j, n_k),
            in_specs=[pl.BlockSpec((tm, tk), x_map),
                      pl.BlockSpec((None, tk, tn), w_map)],
            out_specs=pl.BlockSpec((tm, tn), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((T * tm, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem if vmem > SCOPED_VMEM_DEFAULT else None),
        interpret=interpret,
        name="eaas_grouped_gemm",
        metadata={tracing.STAGE_KEY: "gemm"},
    )(table.tile_gid + group_offset, table.tile_valid, x_pad, w)

    out = gs.unpad_rows(out_pad, padded_idx, row_live)
    tiles = RowTiles(live=table.num_tiles, total=jnp.int32(T))
    return out.astype(x_sorted.dtype), tiles
