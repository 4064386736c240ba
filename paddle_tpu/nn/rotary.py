"""Rotary position embedding with YaRN frequency blending.

``yarn_inv_freq`` gives the inverse frequencies of a rotary slice whose
context was extended by YaRN (Peng et al. 2023, "NTK-by-parts"): dimensions
that turn more than ``beta_fast`` times inside the original context keep
their frequency, those that turn fewer than ``beta_slow`` times are divided
by ``factor``, and a linear ramp blends the ones between. The blend is fixed
when the model is built and applies at every position.

``apply_rope`` rotates pairs of a head's entries by ``position * inv_freq``:
INTERLEAVED pairs (x0, x1), (x2, x3), ... — the layout of DeepSeek's
checkpoints — or, ``layout="half"``, the HALF-SPLIT pairs (x_i, x_{i + D/2})
of the ``rotate_half`` convention (LFM2 and most other published models).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def _correction_dim(turns: float, dim: int, theta: float, original: int):
    return (dim * math.log(original / (turns * 2 * math.pi))
            / (2 * math.log(theta)))


def yarn_inv_freq(dim: int, theta: float, *, factor: float = 1.0,
                  original_max_position: int = 4096, beta_fast: float = 32,
                  beta_slow: float = 1) -> np.ndarray:
    """[dim // 2] float32 inverse frequencies; ``factor`` 1 is plain RoPE."""
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor == 1.0:
        return base.astype(np.float32)
    low = max(math.floor(_correction_dim(beta_fast, dim, theta,
                                         original_max_position)), 0)
    high = min(math.ceil(_correction_dim(beta_slow, dim, theta,
                                         original_max_position)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp                   # 1 where the frequency is kept
    return (base / factor * (1.0 - keep) + base * keep).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, positions, inv_freq, layout: str = "interleaved"):
    """x [..., T, (H,) D] with its pairs along D laid out as ``layout`` says
    ("interleaved" or "half"); positions [..., T] broadcastable to x's
    leading axes. Computed in float32, returned in float32."""
    x = x.astype(jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    if x.ndim == ang.ndim + 1:          # a heads axis between T and D
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if layout == "half":
        half = x.shape[-1] // 2
        x0, x1 = x[..., :half], x[..., half:]
        return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                               axis=-1)
    if layout != "interleaved":
        raise ValueError(f"unknown rotary layout {layout!r}")
    x0, x1 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape)
