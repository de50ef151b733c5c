"""Plain float32 reference of one EAAS MoE layer.

No routing table, buffers, servers or kernels: every held expert is applied
to every token and weighted by the router's normalised top-k scores (its
selection, group-limited where the config says so, is the router's own
:func:`repro.core.router.select`), then the shared experts are added.  A
layer that holds a share of the routed experts gives their part only.  It
reads the expert bank from the per-server primaries of an
:func:`repro.core.moe_layer.init_eaas_moe` params tree, so the layer under
test and the reference see the same weights.

On a TPU a float32 matmul runs in bf16 passes unless told otherwise, so
callers there wrap it in ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core.router import select


def moe_layer_ref(params: Dict, x: jax.Array, cfg_moe: MoEConfig,
                  activation: str = "swiglu") -> jax.Array:
    """x: (T, d) -> (T, d) in float32."""
    assert activation == "swiglu", activation
    f32 = lambda a: a.astype(jnp.float32)
    x = f32(x)
    T = x.shape[0]
    E = cfg_moe.num_experts

    logits = x @ f32(params["router"]["w_router"])
    if cfg_moe.router_score_fn == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    ids, scores = select(probs, cfg_moe,
                         params["router"].get("correction_bias"))
    if cfg_moe.normalize_topk:
        scores = scores / jnp.sum(scores, axis=-1, keepdims=True)
    scores = scores * cfg_moe.routed_scaling_factor
    gate = jnp.zeros((T, cfg_moe.router_width), jnp.float32).at[
        jnp.arange(T)[:, None], ids].set(scores)
    gate = gate[:, cfg_moe.first_expert:cfg_moe.first_expert + E]

    def bank(w):                       # (S, L, a, b) primaries -> (E, a, b)
        S = w.shape[0]
        return f32(w[:, :E // S]).reshape(E, *w.shape[2:])

    sw = params["servers"]
    wg, wu, wd = bank(sw["w_gate"]), bank(sw["w_up"]), bank(sw["w_down"])
    h = (jax.nn.silu(jnp.einsum("td,edf->tef", x, wg))
         * jnp.einsum("td,edf->tef", x, wu))
    y = jnp.einsum("tef,efd->td", h * gate[:, :, None], wd)
    if "shared" in params:
        p = {k: f32(v) for k, v in params["shared"].items()}
        y = y + (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    return y
