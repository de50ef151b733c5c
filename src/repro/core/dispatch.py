"""Client-side token dispatch: pack (token, expert, score) triples into the
per-server shared-buffer slots, and the inverse combine.

Two packing algorithms (both produce identical buffers):

* ``method="sort"``   — stable sort by destination server, O(Tk log Tk).
* ``method="onehot"`` — cumsum-of-onehot ranking, O(Tk · S); no sort, better
  on the VPU when S is small (it is: S = model-axis size, 16).  This is a
  beyond-paper optimization knob; the two methods' buffer-for-buffer
  equivalence is pinned down in tests/test_dispatch.py (property form) and
  tests/test_scenario.py (hypothesis-free form).

Capacity semantics follow the paper's fixed-size buffer slots: at most
``capacity`` tokens per (client, server) pair per layer; overflow tokens are
dropped (counted) exactly as capacity-factor MoE implementations do.  A
route to an expert the layer does not hold (``held`` False: a chip's share
under expert parallelism) is sent nowhere; it takes no slot and is not a
drop.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core.types import DispatchBuffers


def pack(x: jax.Array, expert_ids: jax.Array, scores: jax.Array,
         server_ids: jax.Array, num_servers: int, capacity: int,
         method: str = "onehot", held=None) -> DispatchBuffers:
    """Build request buffers for every destination server.

    x: (T, d); expert_ids/scores/server_ids: (T, k); held: (T, k) bool or
    None (every route held).
    """
    T, d = x.shape
    k = expert_ids.shape[1]
    Tk = T * k
    S, C = num_servers, capacity

    flat_server = server_ids.reshape(Tk)
    flat_expert = expert_ids.reshape(Tk)
    flat_score = scores.reshape(Tk)
    flat_token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    if held is not None:
        # the server past the last: counted nowhere, given no slot
        flat_server = jnp.where(held.reshape(Tk), flat_server, S)

    if method == "sort":
        order = jnp.argsort(flat_server, stable=True)
        s_sorted = flat_server[order]
        counts = jnp.bincount(flat_server, length=S)
        starts = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
        slot_sorted = jnp.arange(Tk, dtype=jnp.int32) - starts[s_sorted].astype(jnp.int32)
        # un-sort the slot assignment back to flat order
        slot = jnp.zeros((Tk,), jnp.int32).at[order].set(slot_sorted)
    elif method == "onehot":
        onehot = jax.nn.one_hot(flat_server, S, dtype=jnp.int32)   # (Tk, S)
        ranks = jnp.cumsum(onehot, axis=0) - onehot                # exclusive
        slot = jnp.take_along_axis(
            ranks, flat_server[:, None].astype(jnp.int32), axis=1)[:, 0]
        counts = jnp.sum(onehot, axis=0)
    else:
        raise ValueError(method)

    valid = slot < C
    if held is not None:
        valid &= held.reshape(Tk)
    flat_idx = jnp.where(valid, flat_server * C + slot, S * C)     # OOB drops

    hidden = jnp.zeros((S * C, d), x.dtype).at[flat_idx].set(
        x[flat_token], mode="drop")
    eid = jnp.full((S * C,), -1, jnp.int32).at[flat_idx].set(
        flat_expert, mode="drop")
    sc = jnp.zeros((S * C,), jnp.float32).at[flat_idx].set(
        flat_score, mode="drop")

    combine_slot = jnp.where(valid, flat_idx, -1).reshape(T, k)
    dropped = jnp.sum(jnp.maximum(counts - C, 0))

    return DispatchBuffers(
        hidden=hidden.reshape(S, C, d),
        expert_id=eid.reshape(S, C),
        score=sc.reshape(S, C),
        counts=jnp.minimum(counts, C).astype(jnp.int32),
        combine_slot=combine_slot,
        dropped=dropped.astype(jnp.int32),
    )


def combine(result_hidden: jax.Array, combine_slot: jax.Array,
            out_dtype=None) -> jax.Array:
    """Sum the k score-weighted expert outputs back per token.

    result_hidden: (S, C, d) server responses (already score-weighted);
    combine_slot: (T, k) flat indices into S*C (-1 = dropped).
    """
    S, C, d = result_hidden.shape
    flat = result_hidden.reshape(S * C, d)
    T, k = combine_slot.shape
    safe = jnp.maximum(combine_slot, 0)
    gathered = flat[safe.reshape(-1)].reshape(T, k, d)
    gathered = jnp.where((combine_slot >= 0)[..., None], gathered, 0)
    out = jnp.sum(gathered.astype(jnp.float32), axis=1)
    return out.astype(out_dtype or result_hidden.dtype)
