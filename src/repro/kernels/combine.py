"""Pallas TPU fused top-k combine: the client-side epilogue of the buffer
protocol — weighted sum of the k returned expert partials per token.

out[t] = sum_k w[t, k] * x[t, k, :].  Grid tiles (tokens, d_model); the tiny
k dimension is kept whole per block and summed by a static loop of
(tokens, d) multiply-adds — Mosaic does not lower the equivalent
``einsum("tkd,tk->td")``, whose contraction dim sits between the others.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)          # (TT, k)
    acc = jnp.zeros(o_ref.shape, jnp.float32)   # (TT, TD)
    for j in range(w.shape[1]):
        acc = acc + x_ref[:, j, :].astype(jnp.float32) * w[:, j:j + 1]
    o_ref[...] = acc.astype(o_ref.dtype)


def combine_weighted_pallas(x: jax.Array, w: jax.Array, *, tt: int = 128,
                            td: int = 512, interpret: bool = False
                            ) -> jax.Array:
    """x: (T, k, d), w: (T, k) -> (T, d).  T % tt == 0, d % td == 0."""
    T, k, d = x.shape
    assert T % tt == 0 and d % td == 0, (T, d, tt, td)
    return pl.pallas_call(
        _kernel,
        grid=(T // tt, d // td),
        in_specs=[
            pl.BlockSpec((tt, k, td), lambda i, j: (i, 0, j)),
            pl.BlockSpec((tt, k), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tt, td), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((T, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, w)
