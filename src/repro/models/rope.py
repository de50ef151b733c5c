"""Rotary position embeddings: standard RoPE, YaRN-scaled RoPE
(DeepSeek-V3) and Qwen2-VL M-RoPE."""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape (head_dim // 2,), fp32."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(head_dim: int, theta: float, scaling) -> jax.Array:
    """YaRN inverse frequencies (DeepSeek-V3's reference code): a linear
    ramp over the frequency index, 0 below the correction dim of
    ``beta_fast`` rotations and 1 above that of ``beta_slow`` (over
    ``original_max_position``), blends each plain frequency with it
    divided by ``factor``."""
    orig = scaling.original_max_position

    def correction_dim(rotations):
        return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv = rope_freqs(head_dim, theta)
    return inv / scaling.factor * ramp + inv * (1.0 - ramp)


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float,
                 scaling=None) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for integer positions; ``scaling`` a
    :class:`~repro.configs.base.YarnScaling` or None.

    positions: (..., seq) int32 -> cos,sin: (..., seq, head_dim//2) fp32
    """
    inv = (rope_freqs(head_dim, theta) if scaling is None
           else yarn_freqs(head_dim, theta, scaling))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (x[..., ::2], x[..., 1::2]).

    x: (..., seq, heads, head_dim); cos/sin broadcastable to
    (..., seq, 1, head_dim//2).
    """
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = x[..., ::2], x[..., 1::2]
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(dtype)


def mrope_cos_sin(positions_thw: jax.Array, head_dim: int, theta: float,
                  sections: Tuple[int, ...]) -> Tuple[jax.Array, jax.Array]:
    """Qwen2-VL multimodal RoPE.

    positions_thw: (3, ..., seq) int32 — temporal/height/width position ids.
    ``sections`` partitions the head_dim//2 frequency slots into (t, h, w)
    groups; each group rotates by its own position stream.
    Returns cos/sin of shape (..., seq, head_dim//2).
    """
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    inv = rope_freqs(head_dim, theta)                     # (hd/2,)
    # angle per stream: (3, ..., seq, hd/2)
    ang = positions_thw.astype(jnp.float32)[..., None] * inv
    # per-frequency-slot stream selection via one-hot contraction
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.array(sections), total_repeat_length=head_dim // 2)
    onehot = jax.nn.one_hot(sec_id, len(sections), dtype=jnp.float32)  # (hd/2, 3)
    ang = jnp.einsum("s...j,js->...j", ang, onehot)
    return jnp.cos(ang), jnp.sin(ang)


def text_mrope_positions(positions: jax.Array) -> jax.Array:
    """For pure-text tokens all three M-RoPE streams share the position."""
    return jnp.broadcast_to(positions[None], (3,) + positions.shape)
