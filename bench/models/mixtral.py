"""The Mixtral family (``model_type`` ``"mixtral"``): grouped-query
attention with rotary embeddings, SwiGLU FFNs, and a router over every
expert the chip holds with top-k scores.

It takes the keys of a Mixtral ``config.json`` and, where a file has them,
DeepSeek's names for a leading dense FFN (``first_k_dense_replace``),
shared experts (``n_shared_experts``, ``moe_intermediate_size``) and
sigmoid scores (``scoring_func``).  What it defines is the contract of
``bench/models/__init__.py``.

The reference computes the model the configuration file states as run:
RMSNorm, grouped-query attention with rotary embeddings on interleaved
pairs, a SwiGLU FFN in the leading dense layers (if any), and in the
others a router over the layer's experts (sigmoid or softmax scores,
top-k, renormalised) whose experts are applied to the tokens routed to
them and weighted by their scores, plus the shared experts (if any).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W
from bench.flops import expert_gemm_work  # noqa: F401  (the contract's)

F32 = jnp.float32
Q_BLOCK = 256          # queries per block of the attention math

# tags folded into a layer's key for the weights this family draws itself
# (bench.weights.TAG holds the shared ones)
_TAG = {"ln1": 0, "ln2": 1, "wq": 2, "wk": 3, "wv": 4, "wo": 5, "router": 9}


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    d_model: int
    num_layers: int
    first_k_dense: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    d_expert: int
    num_experts: int
    top_k: int
    num_shared_experts: int
    vocab: int
    rope_theta: float
    rms_norm_eps: float
    score_fn: str
    normalize_topk: bool

    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense


def _first(cfg: Dict, *keys, default=None):
    """The value of the first of ``keys`` the file has."""
    for k in keys:
        if k in cfg and cfg[k] is not None:
            return cfg[k]
    if default is None:
        raise KeyError(f"{cfg['name']}: none of {keys}")
    return default


def arch(cfg: Dict) -> Arch:
    """The sizes a configuration file states."""
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: only SwiGLU (silu) FFNs")
    if cfg.get("sliding_window") is not None:
        raise ValueError(f"{cfg['name']}: no windowed attention")
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kv_heads = cfg["num_key_value_heads"]
    if heads % kv_heads:
        raise ValueError(f"{cfg['name']}: {heads} query heads do not share "
                         f"{kv_heads} KV heads evenly")
    return Arch(
        name=cfg["name"],
        d_model=d,
        num_layers=cfg["num_hidden_layers"],
        first_k_dense=_first(cfg, "first_k_dense_replace", default=0),
        num_heads=heads,
        num_kv_heads=kv_heads,
        head_dim=_first(cfg, "head_dim", default=d // heads),
        d_ff=cfg["intermediate_size"],
        # Mixtral names its expert width intermediate_size
        d_expert=_first(cfg, "moe_intermediate_size", "intermediate_size"),
        num_experts=_first(cfg, "n_routed_experts", "num_local_experts",
                           "num_experts"),
        top_k=cfg["num_experts_per_tok"],
        num_shared_experts=_first(cfg, "n_shared_experts", default=0),
        vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        score_fn=_first(cfg, "scoring_func", default="softmax"),
        # Mixtral renormalises its top-k scores without a key saying so
        normalize_topk=bool(_first(cfg, "norm_topk_prob", default=True)),
    )


def program_config(cfg: Dict, a: Arch):
    from repro.configs.base import ModelConfig, MoEConfig

    return ModelConfig(
        arch_id=a.name, family="moe", num_layers=a.num_layers,
        d_model=a.d_model, num_heads=a.num_heads,
        num_kv_heads=a.num_kv_heads, d_ff=a.d_ff, vocab_size=a.vocab,
        d_head=a.head_dim, rope_theta=a.rope_theta,
        rms_norm_eps=a.rms_norm_eps, activation="swiglu",
        moe=MoEConfig(num_experts=a.num_experts, top_k=a.top_k,
                      d_expert=a.d_expert,
                      num_shared_experts=a.num_shared_experts,
                      first_k_dense=a.first_k_dense,
                      router_score_fn=a.score_fn,
                      normalize_topk=a.normalize_topk),
        max_seq_len=cfg["serving"]["max_seq"], dtype="bfloat16",
        source=cfg["source"])


# ---------------------------------------------------------------- weights

def attention(lk: jax.Array, a: Arch) -> Dict[str, jax.Array]:
    """One layer's norms and attention projections."""
    k = lambda name: jax.random.fold_in(lk, _TAG[name])
    q_w, kv_w = a.num_heads * a.head_dim, a.num_kv_heads * a.head_dim
    return {"ln1": W._norm(k("ln1"), a.d_model),
            "ln2": W._norm(k("ln2"), a.d_model),
            "wq": W._matrix(k("wq"), a.d_model, q_w),
            "wk": W._matrix(k("wk"), a.d_model, kv_w),
            "wv": W._matrix(k("wv"), a.d_model, kv_w),
            "wo": W._matrix(k("wo"), q_w, a.d_model)}


def router(lk: jax.Array, a: Arch) -> jax.Array:
    return W._matrix(jax.random.fold_in(lk, _TAG["router"]), a.d_model,
                     a.num_experts, F32)


def served_init(a: Arch, want, slots: np.ndarray):
    """``key -> the program's parameter tree``, checked against ``want``
    (:func:`bench.weights.checked`); ``slots`` is the (S, L) expert-id
    layout of its server slots."""
    n_dense = a.first_k_dense

    def dense_layer(k, layer):
        lk = W.layer_key(k, layer)
        w = attention(lk, a)
        return {"ln1": w["ln1"], "ln2": w["ln2"],
                "attn": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
                "mlp": W.ffn(lk, a, a.d_ff)}

    def moe_layer(k, layer):
        lk = W.layer_key(k, layer)
        w = attention(lk, a)
        servers = W.server_banks(lk, a, slots)
        moe = {"router": {"w_router": router(lk, a)}, "servers": servers}
        if a.num_shared_experts:
            moe["shared"] = W.ffn(lk, a, a.d_expert * a.num_shared_experts)
        return {"ln1": w["ln1"], "ln2": w["ln2"],
                "attn": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
                "moe": moe}

    vocab_rows = want["embed"].shape[0]

    def init(k):
        tree = {"embed": W.embed(k, a, vocab_rows),
                "final_ln": W.final_norm(k, a),
                "head": W.head(k, a, vocab_rows),
                # one layer at a time, so the draws of one layer are the
                # only temporaries beside the finished tree
                "blocks": jax.lax.map(lambda l: moe_layer(k, l),
                                      jnp.arange(n_dense, a.num_layers))}
        if n_dense:
            tree["dense_blocks"] = jax.lax.map(lambda l: dense_layer(k, l),
                                               jnp.arange(n_dense))
        return tree

    return W.checked(init, want)


def served_params(a: Arch, seed: int, want, slots: np.ndarray):
    """The program's parameter tree, drawn on the device in one jitted
    call from the seed."""
    return W.on_device(served_init(a, want, slots), seed)


# -------------------------------------------------------------- reference

def _rope(x, pos, theta):
    """x (S, heads, hd) rotated on interleaved pairs at positions pos (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attend(a: Arch, w, x, q):
    """One sequence's attention sublayer with its residual: x (S, d)."""
    S = x.shape[0]
    H, KV, hd = a.num_heads, a.num_kv_heads, a.head_dim
    h = R._rms(x, w["ln1"], a.rms_norm_eps)
    pos = jnp.arange(S)
    qs = _rope(R._lin(h, w["wq"], q).reshape(S, H, hd), pos, a.rope_theta)
    k = _rope(R._lin(h, w["wk"], q).reshape(S, KV, hd), pos, a.rope_theta)
    v = R._lin(h, w["wv"], q).reshape(S, KV, hd)
    g = H // KV

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qs, i * Q_BLOCK, Q_BLOCK)
        qb = qb.reshape(Q_BLOCK, KV, g, hd)
        s = jnp.einsum("qkgh,skh->kgqs", qb, k,
                       precision=R.HI) / math.sqrt(hd)
        qi = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= qi[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", p, v,
                          precision=R.HI).reshape(Q_BLOCK, H * hd)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H * hd)
    return x + R._lin(o, w["wo"], q)


def _gate(a: Arch, w, h):
    """(T, E) gate of each token over the held experts: its top-k scores
    (sigmoid or softmax, renormalised if the file says so), 0 elsewhere."""
    logits = R._mm(h, w["router"])
    probs = (jax.nn.sigmoid(logits) if a.score_fn == "sigmoid"
             else jax.nn.softmax(logits, -1))
    scores, ids = jax.lax.top_k(probs, a.top_k)
    if a.normalize_topk:
        scores = scores / jnp.sum(scores, -1, keepdims=True)
    return jnp.zeros(probs.shape, F32).at[
        jnp.arange(h.shape[0])[:, None], ids].set(scores)


def _layer(a: Arch, w, x, q=None):
    """One decoder layer over a batch of padded sequences: x (B, S, d)."""
    B, S, d = x.shape
    x = jax.lax.map(lambda xi: _attend(a, w, xi, q), x).reshape(B * S, d)

    def ffn(xr):
        h = R._rms(xr, w["ln2"], a.rms_norm_eps)
        return xr + (R.experts(w, h, _gate(a, w, h), q) if "router" in w
                     else R._swiglu(h, w["ffn"], q))
    return R._rows(ffn, x).reshape(B, S, d)


def _draw_layer(a: Arch, key, layer, moe: bool, quant):
    lk = W.layer_key(key, layer)
    att = attention(lk, a)
    w = {n: (v.astype(F32) if n.startswith("ln") else R.quantize(v, quant))
         for n, v in att.items()}
    q = lambda t: {n: R.quantize(v, quant) for n, v in t.items()}
    if not moe:
        w["ffn"] = q(W.ffn(lk, a, a.d_ff))
        return w
    r = router(lk, a)
    # the router is float32 as served: its control rounds it to bfloat16
    w["router"] = r if quant is None else r.astype(jnp.bfloat16).astype(F32)
    w["experts"] = R.draw_experts(lk, a, quant)
    if a.num_shared_experts:
        w["shared"] = q(W.ffn(lk, a, a.d_expert * a.num_shared_experts))
    return w


def reference_layer(a: Arch):
    """``(draw, apply)``: ``draw(key, layer, moe, quant)`` one layer's
    float32 weights, ``apply(w, x, quant=None)`` the layer over x (B, S,
    d)."""
    return ((lambda key, layer, moe, quant:
             _draw_layer(a, key, layer, moe, quant)),
            (lambda w, x, quant=None: _layer(a, w, x, quant)))


def forward(a: Arch, seed: int, seqs, reads, quant=None, pad_to: int = 512,
            batch: int = 0):
    """The float32 reference's logits (:func:`bench.reference.forward`)."""
    return R.forward(a, seed, seqs, reads, layer=reference_layer(a),
                     eps=a.rms_norm_eps, quant=quant, pad_to=pad_to,
                     batch=batch)


# ------------------------------------------------------ operation counts

def dense_flops_per_token(a: Arch) -> float:
    """Matmul operations of one token through the whole model, attention
    scores excluded (see :func:`attention_flops`): the projections, the
    dense FFNs, the top-k routed and the shared experts, the router and
    the head over the vocabulary slice."""
    d = a.d_model
    attn = 2 * d * (2 * a.num_heads * a.head_dim
                    + 2 * a.num_kv_heads * a.head_dim)
    dense_ffn = 2 * 3 * d * a.d_ff
    expert = 2 * 3 * d * a.d_expert
    moe = expert * (a.top_k + a.num_shared_experts) + 2 * d * a.num_experts
    return (a.num_layers * attn + a.first_k_dense * dense_ffn
            + a.moe_layers * moe + 2 * d * a.vocab)


def attention_flops(a: Arch, keys: int) -> float:
    """Score and value operations of one query over ``keys`` positions,
    summed over layers: causal attention over the tokens in context, not
    the cache's full width."""
    return a.num_layers * 2 * 2 * a.num_heads * a.head_dim * keys


def token_flops(a: Arch, position: int) -> float:
    """One token at ``position`` (0-based), attending causally."""
    return dense_flops_per_token(a) + attention_flops(a, position + 1)


def chunk_flops(a: Arch, start: int, length: int) -> float:
    """A prefill chunk of ``length`` tokens starting at ``start``."""
    keys = length * (start + 1) + length * (length - 1) // 2
    return length * dense_flops_per_token(a) + attention_flops(a, keys)
