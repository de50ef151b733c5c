"""The model's weights, made by the benchmark from the seed.

Every weight is a function of ``(seed, layer, name)`` — and, for a routed
expert, of its global expert id — so the served copy and the reference
draw the same numbers without either handing them to the other.  The
served copy is built on the device in one jitted call, in the types the
program serves them in, and laid out as the program's own parameter tree;
the reference draws one layer at a time in float32.

This module holds what every family draws alike (``bench/models``): the
primitives, the embedding, head and final norm, the SwiGLU FFN, the
routed experts and their server slots, and the check of a drawn tree
against the program's.  A family draws its other weights (attention, the
router) under tags of its own, outside :data:`TAG`'s.

Scales keep every layer's output near unit size: matrices are normal with
standard deviation ``1/sqrt(fan_in)``, embeddings are standard normal and
norm scales are ``1 + 0.1 * normal``, so a norm that is skipped shows.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
F32 = jnp.float32

# tags folded into a layer's key, one per weight drawn here
TAG = {"ffn_gate": 6, "ffn_up": 7, "ffn_down": 8, "experts": 10}


def base_key(seed: int) -> jax.Array:
    """A key from a seed of any size up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _matrix(key, fan_in: int, fan_out: int, dtype=BF16) -> jax.Array:
    return (jax.random.normal(key, (fan_in, fan_out), F32)
            * (1.0 / np.sqrt(fan_in))).astype(dtype)


def _norm(key, dim: int) -> jax.Array:
    return 1.0 + 0.1 * jax.random.normal(key, (dim,), F32)


def layer_key(key: jax.Array, layer) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(key, 1), layer)


def embed(key: jax.Array, a, rows: int) -> jax.Array:
    """(rows, d) token embeddings, row ``r`` drawn from its own key, so the
    rows do not depend on how far the table is padded."""
    k = jax.random.fold_in(key, 2)
    return jax.vmap(lambda r: jax.random.normal(
        jax.random.fold_in(k, r), (a.d_model,), F32))(
            jnp.arange(rows)).astype(BF16)


def head(key: jax.Array, a, cols: int) -> jax.Array:
    """(d, cols) output head, column ``c`` drawn from its own key."""
    k = jax.random.fold_in(key, 3)
    return (jax.vmap(lambda c: jax.random.normal(
        jax.random.fold_in(k, c), (a.d_model,), F32))(jnp.arange(cols)).T
        * (1.0 / np.sqrt(a.d_model))).astype(BF16)


def final_norm(key: jax.Array, a) -> jax.Array:
    return _norm(jax.random.fold_in(key, 4), a.d_model)


def ffn(lk: jax.Array, a, width: int) -> Dict[str, jax.Array]:
    """The layer's dense SwiGLU FFN: the dense layers' FFN, or the shared
    experts of an MoE layer (one FFN of their summed width)."""
    k = lambda name: jax.random.fold_in(lk, TAG[name])
    return {"w_gate": _matrix(k("ffn_gate"), a.d_model, width),
            "w_up": _matrix(k("ffn_up"), a.d_model, width),
            "w_down": _matrix(k("ffn_down"), width, a.d_model)}


def expert(lk: jax.Array, a, e) -> Dict[str, jax.Array]:
    """Routed expert ``e`` of one layer."""
    kg, ku, kd = jax.random.split(
        jax.random.fold_in(jax.random.fold_in(lk, TAG["experts"]), e), 3)
    return {"w_gate": _matrix(kg, a.d_model, a.d_expert),
            "w_up": _matrix(ku, a.d_model, a.d_expert),
            "w_down": _matrix(kd, a.d_expert, a.d_model)}


def slot_experts(num_experts: int, num_servers: int,
                 redundant_table) -> np.ndarray:
    """(S, L) expert id held in each server slot (-1: empty), read off the
    program's own loader by laying out a bank whose values are ids."""
    from repro.core.expert_server import build_server_weights

    marker = jnp.arange(1, num_experts + 1, dtype=F32)[:, None, None]
    laid = build_server_weights({"w_gate": marker, "w_up": marker,
                                 "w_down": marker}, num_servers,
                                np.asarray(redundant_table))
    return np.asarray(laid["w_gate"])[..., 0, 0].astype(np.int64) - 1


def server_banks(lk: jax.Array, a, slots: np.ndarray) -> Dict[str, jax.Array]:
    """One MoE layer's routed experts as the program's server slots hold
    them: (S, L, ...) each, expert ``slots[s, j]`` in slot ``j`` of server
    ``s`` and zeros in an empty slot."""
    flat_slots = jnp.asarray(np.maximum(slots, 0).reshape(-1))
    held = jnp.asarray((slots >= 0).reshape(-1))
    S, L = slots.shape

    def slot(inp):
        e, h = inp
        return {n: jnp.where(h, v, 0) for n, v in expert(lk, a, e).items()}
    # slot by slot, so one expert's draws are the only temporaries
    experts = jax.lax.map(slot, (flat_slots, held))
    return {n: v.reshape(S, L, *v.shape[1:]) for n, v in experts.items()}


def checked(init, want):
    """``init``, once the tree it draws matches ``want``, the tree of
    shapes the program's own init would return (``jax.eval_shape``).  A
    leaf the draw does not make, or a shape or type that differs, is an
    error: the layout changed under the benchmark."""
    got = jax.eval_shape(init, jax.random.PRNGKey(0))
    if jax.tree.structure(got) != jax.tree.structure(want):
        raise ValueError("the program's parameter tree changed: "
                         f"{jax.tree.structure(want)} vs the benchmark's "
                         f"{jax.tree.structure(got)}")
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got)):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"parameter {jax.tree_util.keystr(path)}: the "
                             f"program has {w.shape} {w.dtype}, the "
                             f"benchmark draws {g.shape} {g.dtype}")
    return init


def on_device(init, seed: int):
    """The tree ``init`` draws from the seed, in one jitted call on the
    first device."""
    from jax.sharding import SingleDeviceSharding

    return jax.jit(init, out_shardings=SingleDeviceSharding(
        jax.devices()[0]))(base_key(seed))
