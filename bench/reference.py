"""The plain reference: the whole model in float32 ``jax.numpy``.

No cache, no batching across requests, no servers, no kernels.  Each
sequence is run whole (teacher-forced: its prompt and the tokens the
system served), layer by layer, with every matmul at
``Precision.HIGHEST``; the weights are drawn afresh from the seed by
:mod:`bench.weights`, one layer at a time, so float32 copies of a layer are
the only weights on the device at once.

The model it computes is the one the configuration file states as run:
RMSNorm, grouped-query attention with rotary embeddings on interleaved
pairs, a SwiGLU FFN in the leading dense layers (if any), and in the
others a router over the layer's experts (sigmoid or softmax scores,
top-k, renormalised) whose experts are applied to the tokens routed to
them and weighted by their scores, plus the shared experts (if any).

``quant`` gives the lower-precision control: every linear layer computed
in int8 or fp8 — each bf16 weight matrix rounded with one scale per output
column, each input rounded with one scale per token — and the float32
router rounded to bfloat16.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.arch import Arch

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROW_BLOCK = 256        # tokens per block of the FFN / expert math
Q_BLOCK = 256          # queries per block of the attention math


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def quantize(w: jax.Array, kind: Optional[str]) -> jax.Array:
    """``w`` (in, out) in float32, or rounded to ``kind`` with one scale per
    output column and returned in float32."""
    w = w.astype(F32)
    if kind is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    if kind == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if kind == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown control precision {kind!r}")


def _act(x, kind: Optional[str]):
    """Activations entering a matmul: as they are, or rounded to ``kind``
    with one scale per row (per token)."""
    if kind is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    if kind == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _lin(x, w, q):
    """A linear layer whose weights were drawn (and rounded) by
    :func:`_draw_layer`; under a control its input is rounded too."""
    return _mm(_act(x, q), w)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, heads, hd) rotated on interleaved pairs at positions pos (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _swiglu(h, w, q):
    return _lin(jax.nn.silu(_lin(h, w["w_gate"], q)) * _lin(h, w["w_up"], q),
                w["w_down"], q)


def _rows(fn, x):
    """fn over (T, d) rows in blocks of ROW_BLOCK (T a multiple of it)."""
    T, d = x.shape
    out = jax.lax.map(fn, x.reshape(T // ROW_BLOCK, ROW_BLOCK, d))
    return out.reshape(T, -1)


def _attend(a: Arch, w, x, q):
    """One sequence's attention sublayer with its residual: x (S, d)."""
    S = x.shape[0]
    H, KV, hd = a.num_heads, a.num_kv_heads, a.head_dim
    h = _rms(x, w["ln1"], a.rms_norm_eps)
    pos = jnp.arange(S)
    qs = _rope(_lin(h, w["wq"], q).reshape(S, H, hd), pos, a.rope_theta)
    k = _rope(_lin(h, w["wk"], q).reshape(S, KV, hd), pos, a.rope_theta)
    v = _lin(h, w["wv"], q).reshape(S, KV, hd)
    g = H // KV

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qs, i * Q_BLOCK, Q_BLOCK)
        qb = qb.reshape(Q_BLOCK, KV, g, hd)
        s = jnp.einsum("qkgh,skh->kgqs", qb, k, precision=HI) / math.sqrt(hd)
        qi = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= qi[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", p, v,
                          precision=HI).reshape(Q_BLOCK, H * hd)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H * hd)
    return x + _lin(o, w["wo"], q)


def _moe(a: Arch, w, h, q):
    """Routed experts plus shared experts: h (T, d) already normalised."""
    logits = _mm(h, w["router"])
    probs = (jax.nn.sigmoid(logits) if a.score_fn == "sigmoid"
             else jax.nn.softmax(logits, -1))
    scores, ids = jax.lax.top_k(probs, a.top_k)
    if a.normalize_topk:
        scores = scores / jnp.sum(scores, -1, keepdims=True)
    gate = jnp.zeros(probs.shape, F32).at[
        jnp.arange(h.shape[0])[:, None], ids].set(scores)
    ex = w["experts"]
    hq = _act(h, q)
    act = (jax.nn.silu(jnp.einsum("td,edf->tef", hq, ex["w_gate"],
                                  precision=HI))
           * jnp.einsum("td,edf->tef", hq, ex["w_up"], precision=HI))
    y = jnp.einsum("tef,efd->td", _act(act, q) * gate[..., None],
                   ex["w_down"], precision=HI)
    if "shared" in w:
        y = y + _swiglu(h, w["shared"], q)
    return y


def _layer(a: Arch, w, x, q=None):
    """One decoder layer over a batch of padded sequences: x (B, S, d)."""
    B, S, d = x.shape
    x = jax.lax.map(lambda xi: _attend(a, w, xi, q), x).reshape(B * S, d)

    def ffn(xr):
        h = _rms(xr, w["ln2"], a.rms_norm_eps)
        return xr + (_moe(a, w, h, q) if "router" in w
                     else _swiglu(h, w["ffn"], q))
    return _rows(ffn, x).reshape(B, S, d)


def _draw_layer(a: Arch, key, layer, moe: bool, quant):
    lk = W.layer_key(key, layer)
    att = W.attention(lk, a)
    w = {n: (v.astype(F32) if n.startswith("ln") else quantize(v, quant))
         for n, v in att.items()}
    q = lambda t: {n: quantize(v, quant) for n, v in t.items()}
    if not moe:
        w["ffn"] = q(W.ffn(lk, a, a.d_ff))
        return w
    r = W.router(lk, a)
    # the router is float32 as served: its control rounds it to bfloat16
    w["router"] = r if quant is None else r.astype(jnp.bfloat16).astype(F32)
    w["experts"] = jax.lax.map(lambda e: q(W.expert(lk, a, e)),
                               jnp.arange(a.num_experts))
    if a.num_shared_experts:
        w["shared"] = q(W.ffn(lk, a, a.d_expert * a.num_shared_experts))
    return w


def forward(a: Arch, seed: int, seqs: Sequence[np.ndarray],
            reads: Sequence[np.ndarray], quant: Optional[str] = None,
            pad_to: int = 512, batch: int = 0) -> List[np.ndarray]:
    """Logits (float32, over the vocabulary) of each sequence at its read
    positions: ``seqs[i]`` (S_i,) token ids, ``reads[i]`` positions whose
    next-token logits are wanted.  Returns one (len(reads[i]), vocab) array
    per sequence.  Sequences are padded to a multiple of ``pad_to`` and
    the batch to ``batch`` rows, so that a run with the same padding finds
    its programs in the compile cache."""
    key = W.base_key(seed)
    B = max(len(seqs), batch)
    S = max(len(s) for s in seqs)
    S = -(-S // pad_to) * pad_to
    tok = np.zeros((B, S), np.int32)
    for i, s in enumerate(seqs):
        tok[i, :len(s)] = s
    R = -(-max(len(r) for r in reads) // 128) * 128
    at = np.zeros((B, R), np.int32)
    for i, r in enumerate(reads):
        at[i, :len(r)] = r

    x = jax.jit(lambda k, t: W.embed(k, a, a.vocab)[t].astype(F32))(
        key, jnp.asarray(tok))
    draw = {m: jax.jit(lambda k, l, m=m: _draw_layer(a, k, l, m, quant))
            for m in (False, True)}
    step = jax.jit(lambda w, x: _layer(a, w, x, quant), donate_argnums=1)
    for layer in range(a.num_layers):
        w = draw[layer >= a.first_k_dense](key, layer)
        x = step(w, x)
        del w

    def head(k, x, at):
        h = _rms(jnp.take_along_axis(x, at[..., None], 1),
                 W.final_norm(k, a), a.rms_norm_eps)
        return _lin(h, quantize(W.head(k, a, a.vocab), quant), quant)
    logits = np.asarray(jax.jit(head)(key, x, jnp.asarray(at)))
    return [logits[i, :len(r)] for i, r in enumerate(reads)]

