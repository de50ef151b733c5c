"""Pallas TPU grouped GEMM with group-shrink (the paper's expert-server
kernel, §4.1, adapted per DESIGN.md §6).

Computes ``out[i] = x[i] @ w[g(i)]`` for rows sorted by group, where the
tile→group mapping comes from :mod:`repro.kernels.group_shrink` through
scalar prefetch (SMEM).  Grid = (row_tiles, N tiles, K tiles); inactive
groups occupy zero row tiles, dead tail tiles skip the MXU via ``pl.when``.

VMEM working set per grid step: TM·TK (x) + TK·TN (w) + TM·TN·4 (fp32 acc)
— defaults (128, 128, 128) use 96 KiB, far below the ~16 MiB VMEM budget;
larger TN/TK amortize the HBM weight stream better and are swept in
EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import tracing
from repro.kernels import group_shrink as gs


def _kernel(tile_gid, tile_valid, x_ref, w_ref, o_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    i = pl.program_id(0)

    @pl.when(tile_valid[i] > 0)
    def _compute():
        o_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32)


def grouped_gemm_pallas(x_sorted: jax.Array, w: jax.Array,
                        group_sizes: jax.Array, *,
                        tm: int = 128, tn: int = 128, tk: int = 128,
                        interpret: bool = False) -> jax.Array:
    """x_sorted: (M, K) rows sorted by group; w: (G, K, N); -> (M, N).

    Rows beyond ``sum(group_sizes)`` yield zeros.  K and N must be multiples
    of tk/tn (the launch layer pads model dims to 128 already; tests sweep
    unaligned tile choices explicitly).
    """
    M, K = x_sorted.shape
    G, K2, N = w.shape
    assert K == K2, (K, K2)
    assert K % tk == 0 and N % tn == 0, (K, N, tk, tn)

    table = gs.build_tile_table(group_sizes, M, tm)
    x_pad, padded_idx, row_live = gs.pad_rows_to_tiles(
        x_sorted, group_sizes, table, tm)
    T = table.tile_gid.shape[0]

    grid = (T, N // tn, K // tk)
    kernel = functools.partial(_kernel, n_k=K // tk)
    out_pad = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, k, gid, vld: (i, k)),
                pl.BlockSpec((None, tk, tn),
                             lambda i, j, k, gid, vld: (gid[i], k, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda i, j, k, gid, vld: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((T * tm, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="eaas_grouped_gemm",
        metadata={tracing.STAGE_KEY: "gemm"},
    )(table.tile_gid, table.tile_valid, x_pad, w)

    out = gs.unpad_rows(out_pad, padded_idx, row_live)
    return out.astype(x_sorted.dtype)
