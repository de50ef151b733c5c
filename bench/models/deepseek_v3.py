"""The DeepSeek-V3 family (``model_type`` ``"deepseek_v3"``; DeepSeek-R1
publishes the same): multi-head latent attention with YaRN rope, dense
SwiGLU FFNs in the leading layers, and in the others a sigmoid router
over every routed expert of the published model, group-limited with a
score-correction bias, of which this chip holds a share, plus the shared
expert.  What it defines is the contract of ``bench/models/__init__.py``,
and :func:`mla_work`, the least work of the attention that ``mla_roofline``
reads.

The file names its share under ``expert_share``: ``routed_experts`` the
router's published width, ``first_held`` the global id of the first
expert held here, ``chips`` the chips that divide each MoE layer; its
``n_routed_experts`` is the count held.

The reference computes the model as the configuration file states it,
in float32 at ``Precision.HIGHEST``, with no cache: RMSNorm; latent
attention in its expanded form at every position (``c_q = RMSNorm(x
W_qa)``, ``q = c_q W_qb``; ``[c_kv, k_pe] = x W_kva``, ``c_kv =
RMSNorm(c_kv)``, ``[k_nope, v] = c_kv W_kvb``; ``k_pe`` roped once and
shared by the heads; rope on interleaved pairs with YaRN's frequencies,
transcribed here from DeepSeek-V3's reference code; softmax scale
``qk_head_dim**-0.5 * mscale**2``), blocked over sequences and query rows;
the router's sigmoid scores over all routed experts, selection by score
plus bias among the ``topk_group`` groups whose top-2 sum most, weights
the unbiased scores renormalised times ``routed_scaling_factor``, and the
held experts' terms only.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W
from bench.flops import BF16_BYTES, expert_gemm_work  # noqa: F401

F32 = jnp.float32
Q_BLOCK = 256          # queries per block of the attention math
BIAS_STD = 0.05        # the assumed correction bias (the file's "assumed")

# tags folded into a layer's key for the weights this family draws itself
# (bench.weights.TAG holds the shared ones)
_TAG = {"ln1": 0, "ln2": 1, "wq_a": 2, "wq_b": 3, "wkv_a": 4, "wo": 5,
        "router": 9, "wkv_b": 11, "q_norm": 12, "kv_norm": 13, "bias": 14}


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    d_model: int
    num_layers: int
    first_k_dense: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int
    d_expert: int
    num_experts: int           # held here
    routed_experts: int        # the router's width
    first_held: int
    top_k: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    num_shared_experts: int
    vocab: int
    rope_theta: float
    yarn: Tuple                # factor, original, beta_fast, beta_slow,
    #                            mscale_all_dim
    rms_norm_eps: float

    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def arch(cfg: Dict) -> Arch:
    """The sizes a configuration file states; refuses what this family
    does not compute as the file states it."""
    name = cfg["name"]
    want = {"hidden_act": "silu", "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "norm_topk_prob": True,
            "moe_layer_freq": 1, "attention_bias": False,
            "tie_word_embeddings": False}
    for key, value in want.items():
        if cfg.get(key) != value:
            raise ValueError(f"{name}: {key} {cfg.get(key)!r}, this family "
                             f"computes {value!r}")
    rs = cfg["rope_scaling"]
    if rs.get("type") != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError(f"{name}: rope_scaling {rs}, this family computes "
                         "yarn with mscale equal to mscale_all_dim")
    share = cfg.get("expert_share", {})
    routed = share.get("routed_experts", cfg["n_routed_experts"])
    held, first = cfg["n_routed_experts"], share.get("first_held", 0)
    if routed % cfg["n_group"] or not 0 <= first <= routed - held:
        raise ValueError(f"{name}: experts {first}..{first + held - 1} of "
                         f"{routed} in {cfg['n_group']} groups")
    if held * share.get("chips", 1) != routed:
        raise ValueError(f"{name}: {share.get('chips', 1)} chips of "
                         f"{held} experts do not hold {routed}")
    return Arch(
        name=name, d_model=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"], num_experts=held,
        routed_experts=routed, first_held=first,
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        num_shared_experts=cfg["n_shared_experts"],
        vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        yarn=(float(rs["factor"]), int(rs["original_max_position_embeddings"]),
              float(rs["beta_fast"]), float(rs["beta_slow"]),
              float(rs["mscale_all_dim"])),
        rms_norm_eps=float(cfg["rms_norm_eps"]))


def program_config(cfg: Dict, a: Arch):
    from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                    YarnScaling)

    factor, orig, fast, slow, ms_all = a.yarn
    return ModelConfig(
        arch_id=a.name, family="moe", num_layers=a.num_layers,
        d_model=a.d_model, num_heads=a.num_heads,
        num_kv_heads=cfg["num_key_value_heads"], d_ff=a.d_ff,
        vocab_size=a.vocab, d_head=a.qk_nope_head_dim,
        rope_theta=a.rope_theta, rms_norm_eps=a.rms_norm_eps,
        activation="swiglu",
        moe=MoEConfig(num_experts=a.num_experts, top_k=a.top_k,
                      d_expert=a.d_expert,
                      num_shared_experts=a.num_shared_experts,
                      first_k_dense=a.first_k_dense,
                      router_score_fn="sigmoid", normalize_topk=True,
                      n_group=a.n_group, topk_group=a.topk_group,
                      correction_bias=True,
                      routed_scaling_factor=a.routed_scaling_factor,
                      routed_experts=a.routed_experts,
                      first_expert=a.first_held),
        mla=MLAConfig(q_lora_rank=a.q_lora_rank,
                      kv_lora_rank=a.kv_lora_rank,
                      qk_nope_head_dim=a.qk_nope_head_dim,
                      qk_rope_head_dim=a.qk_rope_head_dim,
                      v_head_dim=a.v_head_dim),
        rope_scaling=YarnScaling(factor=factor, original_max_position=orig,
                                 beta_fast=fast, beta_slow=slow,
                                 mscale_all_dim=ms_all),
        max_seq_len=cfg["serving"]["max_seq"], dtype="bfloat16",
        source=cfg["source"])


# ---------------------------------------------------------------- weights

def attention(lk: jax.Array, a: Arch) -> Dict[str, jax.Array]:
    """One layer's norms and latent-attention projections."""
    k = lambda name: jax.random.fold_in(lk, _TAG[name])
    H, r, rope = a.num_heads, a.kv_lora_rank, a.qk_rope_head_dim
    return {"ln1": W._norm(k("ln1"), a.d_model),
            "ln2": W._norm(k("ln2"), a.d_model),
            "wq_a": W._matrix(k("wq_a"), a.d_model, a.q_lora_rank),
            "q_norm": W._norm(k("q_norm"), a.q_lora_rank),
            "wq_b": W._matrix(k("wq_b"), a.q_lora_rank, H * a.qk_head_dim),
            "wkv_a": W._matrix(k("wkv_a"), a.d_model, r + rope),
            "kv_norm": W._norm(k("kv_norm"), r),
            "wkv_b": W._matrix(k("wkv_b"), r,
                               H * (a.qk_nope_head_dim + a.v_head_dim)),
            "wo": W._matrix(k("wo"), H * a.v_head_dim, a.d_model)}


_ATTN = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")


def router(lk: jax.Array, a: Arch) -> Dict[str, jax.Array]:
    """The router over every routed expert, float32, and its correction
    bias (assumed: drawn from the seed)."""
    return {"w_router": W._matrix(jax.random.fold_in(lk, _TAG["router"]),
                                  a.d_model, a.routed_experts, F32),
            "correction_bias": BIAS_STD * jax.random.normal(
                jax.random.fold_in(lk, _TAG["bias"]), (a.routed_experts,),
                F32)}


def _global_slots(a: Arch, slots: np.ndarray) -> np.ndarray:
    """Server slots by global expert id (-1 stays empty)."""
    return np.where(slots >= 0, slots + a.first_held, -1)


def served_init(a: Arch, want, slots: np.ndarray):
    """``key -> the program's parameter tree``, checked against ``want``
    (:func:`bench.weights.checked`); ``slots`` is the (S, L) layout of
    held-expert ids in its server slots."""
    n_dense = a.first_k_dense
    held_slots = _global_slots(a, slots)

    def layer(k, l, moe):
        lk = W.layer_key(k, l)
        w = attention(lk, a)
        out = {"ln1": w["ln1"], "ln2": w["ln2"],
               "attn": {n: w[n] for n in _ATTN}}
        if not moe:
            out["mlp"] = W.ffn(lk, a, a.d_ff)
            return out
        out["moe"] = {"router": router(lk, a),
                      "servers": W.server_banks(lk, a, held_slots),
                      "shared": W.ffn(lk, a,
                                      a.d_expert * a.num_shared_experts)}
        return out

    vocab_rows = want["embed"].shape[0]

    def init(k):
        tree = {"embed": W.embed(k, a, vocab_rows),
                "final_ln": W.final_norm(k, a),
                "head": W.head(k, a, vocab_rows),
                "blocks": jax.lax.map(lambda l: layer(k, l, True),
                                      jnp.arange(n_dense, a.num_layers))}
        if n_dense:
            tree["dense_blocks"] = jax.lax.map(lambda l: layer(k, l, False),
                                               jnp.arange(n_dense))
        return tree

    return W.checked(init, want)


def served_params(a: Arch, seed: int, want, slots: np.ndarray):
    """The program's parameter tree, drawn on the device in one jitted
    call from the seed."""
    return W.on_device(served_init(a, want, slots), seed)


# -------------------------------------------------------------- reference

def yarn_inv_freq(a: Arch) -> np.ndarray:
    """YaRN's inverse frequencies (DeepSeek-V3 ``precompute_freqs_cis``):
    the ramp between the correction dims of beta_fast and beta_slow
    rotations blends theta^(-2i/dim) with it divided by the factor."""
    factor, orig, fast, slow, _ = a.yarn
    dim, base = a.qk_rope_head_dim, a.rope_theta
    freqs = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(fast)), 0)
    high = min(math.ceil(correction_dim(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    smooth = 1 - ramp
    return freqs / factor * (1 - smooth) + freqs * smooth


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(a: Arch) -> float:
    factor, _, _, _, ms_all = a.yarn
    return a.qk_head_dim ** -0.5 * _mscale(factor, ms_all) ** 2


def _rope(x, pos, a: Arch):
    """x (S, heads, dim) rotated on interleaved pairs at positions pos
    (the tables' own temperature, mscale / mscale_all_dim, is 1)."""
    inv = jnp.asarray(yarn_inv_freq(a), F32)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attend(a: Arch, w, x, q):
    """One sequence's latent-attention sublayer, expanded, with its
    residual: x (S, d)."""
    S = x.shape[0]
    H, nope, r = a.num_heads, a.qk_nope_head_dim, a.kv_lora_rank
    eps = a.rms_norm_eps
    h = R._rms(x, w["ln1"], eps)
    pos = jnp.arange(S)
    c_q = R._rms(R._lin(h, w["wq_a"], q), w["q_norm"], eps)
    qs = R._lin(c_q, w["wq_b"], q).reshape(S, H, a.qk_head_dim)
    q_nope, q_pe = qs[..., :nope], _rope(qs[..., nope:], pos, a)
    kv = R._lin(h, w["wkv_a"], q)
    c_kv = R._rms(kv[:, :r], w["kv_norm"], eps)
    k_pe = _rope(kv[:, None, r:], pos, a)[:, 0]
    kvb = R._lin(c_kv, w["wkv_b"], q).reshape(S, H, nope + a.v_head_dim)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scale = softmax_scale(a)

    def block(i):
        sl = lambda t: jax.lax.dynamic_slice_in_dim(t, i * Q_BLOCK, Q_BLOCK)
        s = (jnp.einsum("qhd,shd->hqs", sl(q_nope), k_nope, precision=R.HI)
             + jnp.einsum("qhr,sr->hqs", sl(q_pe), k_pe, precision=R.HI))
        qi = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= qi[:, None], s * scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqs,shd->qhd", p, v, precision=R.HI).reshape(
            Q_BLOCK, H * a.v_head_dim)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, -1)
    return x + R._lin(o, w["wo"], q)


def _gate(a: Arch, w, h):
    """(T, held) gate of each token over the held experts: the routed
    weight of each held expert it chose, 0 elsewhere."""
    s = jax.nn.sigmoid(R._mm(h, w["router"]))              # (T, E)
    T, E = s.shape
    choice = s + w["bias"]
    grouped = choice.reshape(T, a.n_group, E // a.n_group)
    top2 = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)       # (T, groups)
    _, keep = jax.lax.top_k(top2, a.topk_group)
    in_kept = jnp.zeros((T, a.n_group), bool).at[
        jnp.arange(T)[:, None], keep].set(True)
    choice = jnp.where(jnp.repeat(in_kept, E // a.n_group, 1), choice,
                       -jnp.inf)
    _, ids = jax.lax.top_k(choice, a.top_k)
    weights = jnp.take_along_axis(s, ids, -1)
    weights = weights / jnp.sum(weights, -1, keepdims=True)
    gate = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], ids].set(
        weights * a.routed_scaling_factor)
    return gate[:, a.first_held:a.first_held + a.num_experts]


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
    w = {n: (v.astype(F32) if n.endswith("norm") or n.startswith("ln")
             else R.quantize(v, quant))
         for n, v in attention(lk, a).items()}
    q = lambda t: {n: R.quantize(v, quant) for n, v in t.items()}
    if not moe:
        w["ffn"] = q(W.ffn(lk, a, a.d_ff))
        return w
    r = router(lk, a)
    # the router is float32 as served: its control rounds it to bfloat16
    w["router"] = (r["w_router"] if quant is None
                   else r["w_router"].astype(jnp.bfloat16).astype(F32))
    w["bias"] = r["correction_bias"]
    w["experts"] = jax.lax.map(
        lambda e: q(W.expert(lk, a, e)),
        jnp.arange(a.num_experts) + a.first_held)
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

def _projection_flops(a: Arch) -> float:
    """One token's latent-attention projections in one layer: W_qa, W_qb,
    W_kva and W_o (W_kvb is counted by the form that uses it)."""
    d, H = a.d_model, a.num_heads
    return 2 * (d * a.q_lora_rank + a.q_lora_rank * H * a.qk_head_dim
                + d * (a.kv_lora_rank + a.qk_rope_head_dim)
                + H * a.v_head_dim * d)


def _mla_weight_bytes(a: Arch) -> float:
    H, r = a.num_heads, a.kv_lora_rank
    return BF16_BYTES * (
        a.d_model * a.q_lora_rank + a.q_lora_rank * H * a.qk_head_dim
        + a.d_model * (r + a.qk_rope_head_dim)
        + r * H * (a.qk_nope_head_dim + a.v_head_dim)
        + H * a.v_head_dim * a.d_model)


def _absorbed_flops(a: Arch, keys: int) -> float:
    """One query's absorbed attention over ``keys`` cached positions, one
    layer: W_uk into the query, the scores on the latent and the rotary
    key, the values on the latent, W_uv out.  The program runs this form
    for decode and for prefill chunks alike."""
    H, r = a.num_heads, a.kv_lora_rank
    return 2 * H * (a.qk_nope_head_dim * r + (r + a.qk_rope_head_dim) * keys
                    + r * keys + r * a.v_head_dim)


def mla_work(a: Arch, keys: Sequence[int] = (),
             chunks: Sequence[Tuple[int, int]] = ()):
    """(operations, bytes) of one step's latent attention over all layers:
    a decode query for each entry of ``keys`` (the positions it attends,
    itself included), and each ``(start, length)`` prompt chunk, whose
    queries attend causally; all absorbed.  Bytes: the attention weights
    once, and the latent cache read up to each decode query's context and
    once up to each chunk's end."""
    queries = list(keys) + [start + i + 1 for start, length in chunks
                            for i in range(length)]
    if not queries:
        return 0.0, 0.0
    entry = BF16_BYTES * (a.kv_lora_rank + a.qk_rope_head_dim)
    ops = sum(_projection_flops(a) + _absorbed_flops(a, k) for k in queries)
    cached = sum(keys) + sum(start + length for start, length in chunks)
    return (a.num_layers * ops,
            a.num_layers * (_mla_weight_bytes(a) + entry * cached))


def _ffn_flops(a: Arch) -> float:
    """One token's FFN work over the model: the dense layers' FFN, and in
    each MoE layer the routed experts this chip computes on average (top_k
    times its share of the routed experts), the shared expert and the
    router over every routed expert; then the head over the vocabulary
    slice."""
    d = a.d_model
    expert = 2 * 3 * d * a.d_expert
    routed = a.top_k * a.num_experts / a.routed_experts
    moe = (expert * (routed + a.num_shared_experts)
           + 2 * d * a.routed_experts)
    return (a.first_k_dense * 2 * 3 * d * a.d_ff + a.moe_layers * moe
            + 2 * d * a.vocab)


def token_flops(a: Arch, position: int) -> float:
    """One token at ``position`` (0-based), attending causally."""
    return (a.num_layers * (_projection_flops(a)
                            + _absorbed_flops(a, position + 1))
            + _ffn_flops(a))


def chunk_flops(a: Arch, start: int, length: int) -> float:
    """A prefill chunk of ``length`` tokens from ``start``."""
    return sum(token_flops(a, start + i) for i in range(length))
