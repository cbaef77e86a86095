"""What every block's float32 reference shares: matrix products at
``Precision.HIGHEST`` (or rounded to the control's lower precision), RMS
norm and rotary embedding. Plain ``jax.numpy``; nothing of the program.

``quant`` names a lower precision for the control (``"fp8"``): each operand
of every matrix product is rounded to float8 e4m3 with a per-tensor scale
(amax / 448) before the product, as an fp8 serving path would store it.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(a):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(quant: Optional[str]):
    """The matrix product of the reference, or of its control."""
    if quant is None:
        return lambda a, b: jnp.matmul(a, b, precision=HIGHEST)
    if quant == "fp8":
        return lambda a, b: jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST)
    raise ValueError(f"unknown control precision {quant!r}")


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: [L, heads, hd]; rotate the two halves by position."""
    L, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv       # [L, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

