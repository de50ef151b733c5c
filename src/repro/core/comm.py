"""Buffer transfer between attention clients and expert servers.

This is the TPU adaptation of the paper's IBGDA one-sided RDMA library
(DESIGN.md §2).  Each (client, server) buffer slot from
:class:`~repro.core.types.DispatchBuffers` rides one collective:

* ``mode="a2a"``        — tokens are sharded over the server axis too
  (train / prefill): one `all_to_all` moves every slot to its owner.  On ICI
  this lowers to the same one-sided remote-DMA transfers IBGDA issues, but
  scheduled by XLA so it can overlap with compute (double-batch-overlap).
* ``mode="replicated"`` — decode: activations are already replicated across
  the server axis after the attention TP all-reduce, so *no request transfer
  is needed at all*; each server reads its own slot locally and the combine
  is a single psum of the (tiny) per-token outputs.  This is a beyond-paper
  optimization available only because of the disaggregated buffer layout.
* ``mode="local"``      — single-device simulation (tests / CPU examples):
  the identity transfer; the servers are served in one call.

The asymmetry of the paper's protocol ("the server does not initiate any
communication") is preserved structurally: transfers appear only in
client-side code; server code (expert_server.py) is a pure function from its
received slots to its result slots.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.types import DispatchBuffers


def send_to_servers(buffers: DispatchBuffers, axis_name: Optional[str],
                    mode: str):
    """Client half of the request transfer.

    Returns (hidden, expert_id, score, counts) as seen by the local server:
      a2a/local:  hidden (S, C, d) — dim0 = source client
      replicated: hidden (1, C, d) — this server's own slot (selected locally)
    """
    if mode == "local" or axis_name is None:
        return buffers.hidden, buffers.expert_id, buffers.score, buffers.counts

    if mode == "a2a":
        a2a = lambda x: jax.lax.all_to_all(
            x, axis_name, split_axis=0, concat_axis=0, tiled=True)
        return (a2a(buffers.hidden), a2a(buffers.expert_id),
                a2a(buffers.score), a2a(buffers.counts))

    if mode == "replicated":
        rank = jax.lax.axis_index(axis_name)
        sel = lambda x: jax.lax.dynamic_slice_in_dim(x, rank, 1, axis=0)
        return (sel(buffers.hidden), sel(buffers.expert_id),
                sel(buffers.score), sel(buffers.counts))

    raise ValueError(mode)


def return_to_clients(result_hidden: jax.Array, axis_name: Optional[str],
                      mode: str) -> jax.Array:
    """Server→client response transfer (the read-result half of the slot).

    result_hidden: (S_src, C, d) for a2a/local (dim0 = source client, i.e.
    where each slot must go back to), or (1, C, d) for replicated.
    Returns (S, C, d) per client — dim0 = responding server.
    """
    if mode == "local" or axis_name is None:
        return result_hidden
    if mode == "a2a":
        return jax.lax.all_to_all(
            result_hidden, axis_name, split_axis=0, concat_axis=0, tiled=True)
    if mode == "replicated":
        # Place my slice at my rank; combine()'s masked gather + psum does the
        # rest (dispatch.combine is linear in the result buffer).
        S = jax.lax.axis_size(axis_name)
        rank = jax.lax.axis_index(axis_name)
        C, d = result_hidden.shape[1:]
        buf = jnp.zeros((S, C, d), result_hidden.dtype)
        return jax.lax.dynamic_update_slice_in_dim(buf, result_hidden, rank, 0)
    raise ValueError(mode)


def finalize_combine(y_partial: jax.Array, axis_name: Optional[str],
                     mode: str) -> jax.Array:
    """Cross-server reduction of the combined output (replicated mode only)."""
    if mode == "replicated" and axis_name is not None:
        return jax.lax.psum(y_partial, axis_name)
    return y_partial
