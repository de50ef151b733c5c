"""MoE gating: softmax / sigmoid scoring, top-k selection, aux losses.

The router runs on the attention client (paper Fig. 4): it is part of the
dense tier, so its weights are replicated over clients and it is computed in
fp32 (routing decisions must agree bit-exactly across replicas).

It scores every routed expert of the published model
(``MoEConfig.router_width``), also on a chip that holds only a share of
them.  DeepSeek-V3's ``noaux_tc`` selection is group-limited: selection
scores ``s + b`` (``b`` the learned correction bias, when the config has
one) rank the ``n_group`` groups by the sum of their top-2, and top-k is
taken inside the ``topk_group`` best; the weights are the unbiased scores
at the chosen ids, renormalised, times ``routed_scaling_factor``.  With
``n_group`` 1 and no bias the selection is plain top-k, the same program
as before either existed.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig


def init_router(key, d_model: int, num_experts: int,
                correction_bias: bool = False) -> Dict:
    # fp32: router logits are tiny but numerically sensitive
    w = jax.random.normal(key, (d_model, num_experts), jnp.float32) * 0.02
    if correction_bias:
        return {"w_router": w,
                "correction_bias": jnp.zeros((num_experts,), jnp.float32)}
    return {"w_router": w}


def select(probs: jax.Array, cfg: MoEConfig, correction_bias=None):
    """(ids (T, k), unbiased scores at them) of each token's top_k experts,
    group-limited when ``cfg.n_group`` > 1; ``correction_bias`` (E,)
    steers the choice, not the scores."""
    choice = probs if correction_bias is None else probs + correction_bias
    if cfg.n_group > 1:
        T, E = choice.shape
        grouped = choice.reshape(T, cfg.n_group, E // cfg.n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, cfg.topk_group)   # (T, g)
        allowed = jnp.zeros((T, cfg.n_group), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        choice = jnp.where(jnp.repeat(allowed, E // cfg.n_group, axis=1),
                           choice, -jnp.inf)
    if choice is probs:              # plain top-k: the values are the scores
        scores, ids = jax.lax.top_k(probs, cfg.top_k)
        return ids, scores
    _, ids = jax.lax.top_k(choice, cfg.top_k)
    return ids, jnp.take_along_axis(probs, ids, axis=-1)


def route(params: Dict, x: jax.Array, cfg: MoEConfig,
          bias: jax.Array = None):
    """x: (T, d) -> RouterOutput over ``cfg.router_width`` experts (global
    ids) with cfg.top_k.

    ``bias``: optional (E,) fp32 logit offset added before scoring —
    runtime *data*, not params.  The serving tier uses it to shape expert
    traffic (scenario ``set_skew``: Zipf-skewed / shifting-hot-set traces);
    zeros reproduce the unbiased router bit-exactly.
    """
    from repro.core.types import RouterOutput

    # full f32 even on TPU, whose default f32 matmul is one bf16 pass: a
    # near-tie then flips the top-k between replicas and the reference
    logits = jnp.matmul(x.astype(jnp.float32), params["w_router"],
                        precision=jax.lax.Precision.HIGHEST)  # (T, E)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if cfg.router_score_fn == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif cfg.router_score_fn == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(cfg.router_score_fn)

    expert_ids, scores = select(probs, cfg,
                                params.get("correction_bias"))  # (T, k)
    if cfg.normalize_topk:
        scores = scores / jnp.maximum(
            jnp.sum(scores, axis=-1, keepdims=True), 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        scores = scores * cfg.routed_scaling_factor

    # Switch-style load-balance loss: E * sum_e f_e * p_e
    T, E = probs.shape
    assign = jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32)
    f = jnp.mean(assign, axis=0)                  # fraction routed (top-1)
    p = jnp.mean(probs, axis=0)                   # mean router prob
    aux = E * jnp.sum(f * p) * cfg.router_aux_loss_coef

    lse = jax.nn.logsumexp(logits, axis=-1)
    z = jnp.mean(jnp.square(lse)) * cfg.router_z_loss_coef

    return RouterOutput(
        expert_ids=expert_ids.astype(jnp.int32),
        scores=scores,
        full_probs=probs,
        aux_loss=aux,
        z_loss=z,
    )


def expert_load(expert_ids: jax.Array, num_experts: int) -> jax.Array:
    """Token count per expert (the statistic fed to the load balancer)."""
    return jnp.bincount(expert_ids.reshape(-1), length=num_experts)
