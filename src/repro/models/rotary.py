"""Rotary position embeddings, including partial rotary (stablelm-2: 25%)."""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

__all__ = ["rope_frequencies", "apply_rope"]


def rope_frequencies(rot_dim: int, positions: jnp.ndarray,
                     theta: float = 10000.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables (..., rot_dim/2) for integer positions (...,)."""
    assert rot_dim % 2 == 0
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., rot_dim/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               rot_dim: int | None = None, head_axis: int = -2) -> jnp.ndarray:
    """Rotate the interleaved pairs (x[2i], x[2i+1]) of the first
    ``rot_dim`` features of x (..., S, H, head_dim), or of x
    (..., H, S, head_dim) with ``head_axis=-3``; cos/sin are
    (..., S, rot_dim/2) and broadcast over the head axis.

    Written over the whole feature axis as x * cos + swap(x) * sin, with
    swap(x)[2i] = -x[2i+1], swap(x)[2i+1] = x[2i], cos 1 and sin 0 past
    ``rot_dim``: lane rotations and elementwise ops only, so XLA keeps the
    projection's layout (strided pair slices lower to gathers on a TPU and
    force relayout copies)."""
    hd = x.shape[-1]
    if rot_dim is None:
        rot_dim = hd
    if rot_dim == 0:
        return x
    rest = [(0, 0)] * (cos.ndim - 1) + [(0, (hd - rot_dim) // 2)]
    c = jnp.repeat(jnp.pad(cos, rest, constant_values=1.0), 2, axis=-1)
    s = jnp.repeat(jnp.pad(sin, rest), 2, axis=-1)
    even = jnp.arange(hd) % 2 == 0
    swap = jnp.where(even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))
    return (x * jnp.expand_dims(c, head_axis)
            + swap * jnp.expand_dims(s, head_axis))
