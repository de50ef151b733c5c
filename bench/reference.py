"""The plain reference's shared part: float32 ``jax.numpy``, no cache, no
batching across requests, no servers, no kernels.

Each sequence is run whole (teacher-forced: its prompt and the tokens the
system served), layer by layer, with every matmul at
``Precision.HIGHEST``; the weights are drawn afresh from the seed by
:mod:`bench.weights`, one layer at a time, so float32 copies of a layer are
the only weights on the device at once.  A family module (``bench/models``)
gives :func:`forward` its layer, drawn and applied; this module holds the
loop with its padding and head, and what every family computes alike:
RMSNorm, the SwiGLU FFN, and the routed experts this chip holds applied to
each token under a gate the family's routing computed, plus the shared
experts (if any).

``quant`` gives the lower-precision control: every linear layer computed
in int8 or fp8 — each bf16 weight matrix rounded with one scale per output
column, each input rounded with one scale per token — and the float32
router rounded to bfloat16.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROW_BLOCK = 256        # tokens per block of the FFN / expert math


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
    """A linear layer whose weights were drawn (and rounded by
    :func:`quantize`); under a control its input is rounded too."""
    return _mm(_act(x, q), w)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _swiglu(h, w, q):
    return _lin(jax.nn.silu(_lin(h, w["w_gate"], q)) * _lin(h, w["w_up"], q),
                w["w_down"], q)


def _rows(fn, x):
    """fn over (T, d) rows in blocks of ROW_BLOCK (T a multiple of it)."""
    T, d = x.shape
    out = jax.lax.map(fn, x.reshape(T // ROW_BLOCK, ROW_BLOCK, d))
    return out.reshape(T, -1)


def draw_experts(lk, a, quant) -> Dict:
    """The routed experts this chip holds, ids 0 .. ``a.num_experts`` - 1
    (stacked, (E, ...) each), in float32, rounded under a control."""
    q = lambda t: {n: quantize(v, quant) for n, v in t.items()}
    return jax.lax.map(lambda e: q(W.expert(lk, a, e)),
                       jnp.arange(a.num_experts))


def experts(w, h, gate, q):
    """The held experts ``w["experts"]`` applied to h (T, d), already
    normalised, each weighted by the token's gate (T, E) (0 where it was
    not routed), plus the shared experts ``w["shared"]`` if the layer has
    them."""
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


def forward(a, seed: int, seqs: Sequence[np.ndarray],
            reads: Sequence[np.ndarray], *, layer: Tuple[Callable, Callable],
            eps: float, quant: Optional[str] = None, pad_to: int = 512,
            batch: int = 0) -> List[np.ndarray]:
    """Logits (float32, over the vocabulary) of each sequence at its read
    positions: ``seqs[i]`` (S_i,) token ids, ``reads[i]`` positions whose
    next-token logits are wanted.  Returns one (len(reads[i]), vocab) array
    per sequence.  ``layer`` is the family's ``(draw, apply)``
    (``reference_layer``), ``eps`` the final norm's epsilon.  Sequences are
    padded to a multiple of ``pad_to`` and the batch to ``batch`` rows, so
    that a run with the same padding finds its programs in the compile
    cache."""
    draw_layer, apply_layer = layer
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
    draw = {m: jax.jit(lambda k, l, m=m: draw_layer(k, l, m, quant))
            for m in (False, True)}
    step = jax.jit(lambda w, x: apply_layer(w, x, quant), donate_argnums=1)
    for layer in range(a.num_layers):
        w = draw[layer >= a.first_k_dense](key, layer)
        x = step(w, x)
        del w

    def head(k, x, at):
        h = _rms(jnp.take_along_axis(x, at[..., None], 1),
                 W.final_norm(k, a), eps)
        return _lin(h, quantize(W.head(k, a, a.vocab), quant), quant)
    logits = np.asarray(jax.jit(head)(key, x, jnp.asarray(at)))
    return [logits[i, :len(r)] for i, r in enumerate(reads)]

