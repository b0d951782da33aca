"""Rotary position embeddings with Llama-3 or YaRN frequency scaling, in the
half-split and the interleaved pairing.

Computed on the fly from integer positions (no host-precomputed cos/sin
tables): a gather from a [max_pos, hd] table would be HBM-bound, while
computing cos/sin in-register is VPU work that XLA fuses into the attention
prologue — the TPU-friendly trade.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax.numpy as jnp


def rope_frequencies(
    head_dim: int,
    theta: float,
    scaling: Optional[Dict[str, Any]] = None,
) -> jnp.ndarray:
    """Inverse frequencies [head_dim//2], with optional llama3-style scaling."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta**exponents)
    if scaling and scaling.get("rope_type", scaling.get("type")) == "llama3":
        factor = scaling["factor"]
        low = scaling.get("low_freq_factor", 1.0)
        high = scaling.get("high_freq_factor", 4.0)
        orig = scaling.get("original_max_position_embeddings", 8192)
        # Long wavelengths (low freqs) scaled down by `factor`; short kept;
        # the band between orig/low and orig/high blends linearly.
        wavelen = 2.0 * math.pi / inv_freq
        smooth = jnp.clip((orig / wavelen - low) / (high - low), 0.0, 1.0)
        blended = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
        inv_freq = jnp.where(
            wavelen > orig / low,
            inv_freq / factor,
            jnp.where(wavelen < orig / high, inv_freq, blended),
        )
    elif scaling and scaling.get("rope_type", scaling.get("type")) == "yarn":
        inv_freq = yarn_frequencies(inv_freq, head_dim, theta, scaling)
    return inv_freq


def yarn_frequencies(inv_freq, dim: int, theta: float, scaling: Dict[str, Any]):
    """YaRN (arXiv:2309.00071) as DeepSeek-V3 applies it: dimensions that turn
    more than ``beta_fast`` times over the original context keep their
    frequency, those that turn fewer than ``beta_slow`` times are divided by
    ``factor``, and a linear ramp over the dimension index blends between."""
    factor = float(scaling["factor"])
    orig = scaling.get("original_max_position_embeddings", 4096)

    def correction_dim(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2.0 * math.pi)) / (2.0 * math.log(theta))

    low = max(math.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(scaling.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return inv_freq / factor * ramp + inv_freq * (1.0 - ramp)


def yarn_mscale(scaling: Optional[Dict[str, Any]]) -> float:
    """The factor m whose square multiplies the softmax scale under YaRN:
    0.1 * mscale_all_dim * ln(factor) + 1 (1.0 without scaling)."""
    if not scaling or scaling.get("rope_type", scaling.get("type")) != "yarn":
        return 1.0
    factor, m = float(scaling["factor"]), float(scaling.get("mscale_all_dim", 0) or 0)
    if factor <= 1.0 or m == 0.0:
        return 1.0
    return 0.1 * m * math.log(factor) + 1.0


def apply_rope_interleaved(
    x: jnp.ndarray,  # [..., seq, heads, head_dim]
    positions: jnp.ndarray,  # [..., seq] int32
    inv_freq: jnp.ndarray,  # [head_dim//2]
) -> jnp.ndarray:
    """Rotate the adjacent pairs (x[2i], x[2i+1]) by angle i, in place: the
    pairing of DeepSeek's latent attention."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def apply_rope(
    x: jnp.ndarray,  # [..., seq, heads, head_dim]
    positions: jnp.ndarray,  # [..., seq] int32
    inv_freq: jnp.ndarray,  # [head_dim//2]
) -> jnp.ndarray:
    """Rotate pairs (x[2i], x[2i+1]) — interleaved convention folded to
    half-split (HF llama convention: first/second half pairing)."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., seq, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
