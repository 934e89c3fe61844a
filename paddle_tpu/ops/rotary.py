"""Rotary position embedding from a configuration's ``rope_parameters``
entry: the frequencies of each rotated pair and the scale of ``cos`` /
``sin``, and the rotation by halves that applies them.

``frequencies(params, head_dim) -> (inv (D/2,) float32, scale)``:

* ``rope_type`` ``default``: ``inv_i = theta ** (-2i / D)``, scale 1;
* ``yarn`` (arXiv:2309.00071, as ``transformers`` computes it):
  ``inv_extra_i = theta ** (-2i / D)``, ``inv_inter_i = inv_extra_i /
  factor``; with ``corr(r) = D ln(original / (2 pi r)) / (2 ln theta)``,
  ``low = floor(corr(beta_fast))`` and ``high = ceil(corr(beta_slow))``
  (clamped to ``[0, D - 1]``), ``ramp_i = clamp((i - low) / (high - low), 0,
  1)`` and ``inv_i = inv_inter_i ramp_i + inv_extra_i (1 - ramp_i)``: pairs
  below ``low`` keep their frequency, pairs above ``high`` turn ``factor``
  times slower. ``scale`` is ``attention_factor`` (default ``0.1 ln(factor)
  + 1``), which multiplies ``cos`` and ``sin`` and so every score of the
  layer by its square.

``rotate_halves(x, pos, inv, scale)`` turns ``x`` (..., T, H, D) at
positions ``pos`` (..., T): ``[x1 cos - x2 sin, x2 cos + x1 sin]`` over the
halves ``x1 = x[..., :R/2]``, ``x2 = x[..., R/2:R]`` (``rotate_half``) of the
first ``R = 2 len(inv)`` dimensions, the rest passed through (a partial
rotary: ``frequencies(params, R)``), in float32, back in ``x``'s dtype.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax.numpy as jnp
import numpy as np

__all__ = ["frequencies", "rotate_halves"]


def frequencies(params: Dict, head_dim: int) -> Tuple[np.ndarray, float]:
    """``(inv (head_dim / 2,) float32, scale)`` of one ``rope_parameters``
    entry (the module docstring has the formulas)."""
    d = int(head_dim)
    theta = float(params["rope_theta"])
    kind = params.get("rope_type", "default")
    extra = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if kind == "default":
        return extra.astype(np.float32), 1.0
    if kind != "yarn":
        raise NotImplementedError(f"rope_type {kind!r}")
    factor = float(params["factor"])
    original = float(params["original_max_position_embeddings"])

    def corr(rotations: float) -> float:
        return d * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(float(params.get("beta_fast", 32)))), 0)
    high = min(math.ceil(corr(float(params.get("beta_slow", 1)))), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    scale = params.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(scale)


def rotate_halves(x, pos, inv, scale: float = 1.0):
    """``x`` (..., T, H, D) at positions ``pos`` (..., T), its first ``2
    len(inv)`` dimensions turned by ``pos * inv`` with ``cos`` and ``sin``
    times ``scale``."""
    half = int(np.shape(inv)[-1])
    ang = pos.astype(jnp.float32)[..., None, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x32 = x.astype(jnp.float32)
    x1, x2, rest = x32[..., :half], x32[..., half:2 * half], \
        x32[..., 2 * half:]
    turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    return jnp.concatenate(turned + ([rest] if rest.shape[-1] else []),
                           axis=-1).astype(x.dtype)
