"""Float32 building blocks every reference module shares, in ``jax.numpy``.

A configuration's layer equations are in its reference module
(``references/<name>.py``); what they are built from is here: a linear
layer, RMSNorm and rotary positions at ``Precision.HIGHEST``, and the
visual rows that enter the backbone. It imports nothing of the program.

``linear(..., quant="fp8")`` is the control: every weight and activation
entering a linear layer is rounded to float8 (e4m3, per-tensor scale for
weights, per-row for activations) before a float32 product -- the
precision a later program might be tempted to serve in.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def linear(spec, x, w, quant):
    """``einsum(spec, x, w)`` in float32; the control rounds both sides."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x = q8(x, -1)
        w = q8(w, None)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, pos, theta, fraction):
    """Rotary embedding, rotate-half pairing, on the first ``fraction`` of
    each head (``partial_rotary_factor``); x [S, H, D], pos [S]."""
    d = int(x.shape[-1] * fraction)
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., : d // 2], x[..., d // 2: d], x[..., d:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def select_visual(raw: np.ndarray, keep: Optional[int]) -> np.ndarray:
    """The visual rows that enter the backbone. ``keep`` rows of lowest
    L2 norm, in their original order -- the salience the program's
    FastV strategy uses in place of attention -- or all rows."""
    if keep is None or keep >= len(raw):
        return raw
    norms = np.linalg.norm(raw.astype(np.float64), axis=-1)
    return raw[np.sort(np.argsort(norms, kind="stable")[:keep])]
