"""jit'd public wrappers around the Pallas kernels.

Off the TPU the kernels run in interpret mode (the CPU test path); on the TPU
they always compile through Mosaic. ``interpret()`` decides when a kernel is
traced, never when this module is imported. The wrappers handle
padding/reshaping so arbitrary model shapes hit hardware-aligned kernel
tiles.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import flash_attention
from .fused_update import (fused_adamw_1d, fused_adamw_ref, fused_lars_1d,
                           fused_lars_ref, fused_sgd_1d, fused_sgd_ref)
from .gossip_mix import LANE, gossip_mix_1d, gossip_mix_2d, gossip_mix_q2d
from .quantize import dequant_tiles
from .ssm_scan import ssm_scan_chunked

PyTree = Any

__all__ = ["interpret", "gossip_mix_flat", "gossip_mix_tree",
           "gossip_mix_bucket", "gossip_mix_wire_bucket", "fused_sgd_bucket",
           "fused_adamw_bucket", "fused_lars_bucket", "ssm_scan",
           "flash_mha"]


def interpret() -> bool:
    """True unless the default backend is a TPU. A backend that fails to
    start raises here rather than quietly turning kernels into their
    interpreter."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("alpha",))
def gossip_mix_flat(a: jnp.ndarray, b: jnp.ndarray,
                    alpha: float = 0.5) -> jnp.ndarray:
    """Mix two same-shape buffers of any shape via the tiled kernel.

    Ragged lengths are handled natively by ``gossip_mix_1d`` (aligned prefix
    through the kernel, < LANE tail in a jnp epilogue) — no full-buffer pad
    copy."""
    return gossip_mix_1d(a.reshape(-1), b.reshape(-1), alpha=alpha,
                         interpret=interpret()).reshape(a.shape)


def gossip_mix_tree(a: PyTree, b: PyTree, alpha: float = 0.5) -> PyTree:
    """Per-leaf kernel mix — a drop-in ``mix_impl`` for core.gossip
    (signature (local, received, alpha))."""
    return jax.tree.map(lambda x, y: gossip_mix_flat(x, y, alpha=alpha), a, b)


def gossip_mix_bucket(a: jnp.ndarray, b: jnp.ndarray,
                      alpha: float = 0.5) -> jnp.ndarray:
    """Mix one persistent gossip bucket in place.

    Buckets are LANE-aligned by construction (core.buckets.BucketLayout), so
    this is a single aliased kernel call — no pad, no tail, no cast: the
    donation-friendly hot path of the packed gossip engine. Takes the
    bucket's ``(..., rows, 128)`` storage as it comes (any leading axes,
    e.g. the sharded replica axis): the kernel's view is a bitcast of it.
    """
    n = int(np.prod(a.shape))
    assert n % LANE == 0, f"bucket size {a.shape} not LANE-aligned"
    itp = interpret()
    out = gossip_mix_2d(a.reshape(-1, LANE), b.reshape(-1, LANE), alpha=alpha,
                        interpret=itp, donate=not itp)
    return out.reshape(a.shape)


def gossip_mix_wire_bucket(a: jnp.ndarray, payload, alpha=0.5) -> jnp.ndarray:
    """Mix one bucket against an arrived WIRE payload.

    ``payload`` is either a raw array (fp32/bf16 wire — dtype-promoting mix,
    same kernel as ``gossip_mix_bucket``) or a quantized ``{"q", "s"}`` dict
    (int8/fp8 codes + per-(row, 128)-tile fp32 scales), whose decode folds
    into the mix sweep via the scale column stream — bit-identical to
    ``kernels.quantize.dequant_tiles`` followed by the plain mix."""
    if not isinstance(payload, dict):
        return gossip_mix_bucket(a, payload, alpha=alpha)
    n = int(np.prod(a.shape))
    assert n % LANE == 0, f"bucket size {a.shape} not LANE-aligned"
    itp = interpret()
    out = gossip_mix_q2d(a.reshape(-1, LANE),
                         payload["q"].reshape(-1, LANE),
                         payload["s"].reshape(-1), alpha=alpha,
                         interpret=itp, donate=not itp)
    return out.reshape(a.shape)


def _fused_impl(impl: Optional[str]) -> str:
    """Backend choice for the fused mix+apply update kernels.

    ``None`` (auto): the Pallas kernel on TPU (with buffer donation), the jnp
    twin elsewhere — same math, XLA-fused into one sweep, without
    interpret-mode overhead in the CPU hot loop.  ``"pallas"`` forces the
    kernel (interpret mode off-TPU — the validation path), ``"jnp"`` forces
    the twin.
    """
    if impl is None:
        return "jnp" if interpret() else "pallas"
    if impl not in ("pallas", "jnp"):
        raise ValueError(f"unknown fused-update impl {impl!r}")
    return impl


def fused_sgd_bucket(p, g, partner, mom, *, lr, alpha=0.5, momentum=0.9,
                     weight_decay=0.0, impl: Optional[str] = None):
    """Single-sweep fused mix+SGD over one persistent gossip bucket:
    ``mixed = (1-alpha)*p + alpha*partner`` then the SGD-momentum update at
    the mixed point, one read + one write pass, donation-friendly.  Takes
    the bucket's ``(..., rows, 128)`` storage as it comes (the kernel's view
    is a bitcast of it) and ragged (non-LANE) flat buffers via the kernel's
    tail epilogue.  A quantized
    wire partner (``{"q", "s"}`` dict, see kernels.quantize) is decoded
    in-kernel on the Pallas path and pre-decoded (bit-identically) on the
    jnp path."""
    scales = None
    if isinstance(partner, dict):
        if _fused_impl(impl) == "jnp":
            partner = dequant_tiles(partner["q"], partner["s"])
        else:
            partner, scales = partner["q"], partner["s"]
    if _fused_impl(impl) == "jnp":
        return fused_sgd_ref(p, g, partner, mom, lr=lr, alpha=alpha,
                             momentum=momentum, weight_decay=weight_decay)
    return fused_sgd_1d(p, g, partner, mom, lr=lr, alpha=alpha,
                        momentum=momentum, weight_decay=weight_decay,
                        partner_scales=scales,
                        interpret=interpret(), donate=not interpret())


def fused_adamw_bucket(p, g, partner, m, v, *, lr, c1, c2, alpha=0.5, b1=0.9,
                       b2=0.95, eps=1e-8, weight_decay=0.0,
                       impl: Optional[str] = None):
    """Single-sweep fused mix+AdamW over one bucket (see fused_sgd_bucket);
    quantized wire partners decode in the same sweep."""
    scales = None
    if isinstance(partner, dict):
        if _fused_impl(impl) == "jnp":
            partner = dequant_tiles(partner["q"], partner["s"])
        else:
            partner, scales = partner["q"], partner["s"]
    if _fused_impl(impl) == "jnp":
        return fused_adamw_ref(p, g, partner, m, v, lr=lr, c1=c1, c2=c2,
                               alpha=alpha, b1=b1, b2=b2, eps=eps,
                               weight_decay=weight_decay)
    return fused_adamw_1d(p, g, partner, m, v, lr=lr, c1=c1, c2=c2,
                          alpha=alpha, b1=b1, b2=b2, eps=eps,
                          weight_decay=weight_decay, partner_scales=scales,
                          interpret=interpret(), donate=not interpret())


def fused_lars_bucket(p, g, partner, mom, row_scale, *, lr, alpha=0.5,
                      momentum=0.9, weight_decay=0.0,
                      impl: Optional[str] = None):
    """Single-sweep fused mix+LARS over one bucket, with the per-row trust
    scale from the norm prepass (see optim.lars's fused backend)."""
    if _fused_impl(impl) == "jnp":
        return fused_lars_ref(p, g, partner, mom, row_scale, lr=lr,
                              alpha=alpha, momentum=momentum,
                              weight_decay=weight_decay)
    return fused_lars_1d(p, g, partner, mom, row_scale, lr=lr, alpha=alpha,
                         momentum=momentum, weight_decay=weight_decay,
                         interpret=interpret(), donate=not interpret())


@functools.partial(jax.jit, static_argnames=("chunk", "block_d"))
def ssm_scan(dA: jnp.ndarray, dBx: jnp.ndarray, chunk: int = 128,
             block_d: int = 256) -> jnp.ndarray:
    """(B,S,D,N) selective scan via the chunked kernel; pads S to a chunk
    multiple and D to a block multiple."""
    B, S, D, N = dA.shape
    ch = min(chunk, S)
    bd = min(block_d, D)
    Sp = -(-S // ch) * ch
    Dp = -(-D // bd) * bd
    padded = (Sp != S) or (Dp != D)
    if padded:
        padw = ((0, 0), (0, Sp - S), (0, Dp - D), (0, 0))
        dA = jnp.pad(dA, padw)
        dBx = jnp.pad(dBx, padw)
    h = ssm_scan_chunked(dA, dBx, chunk=ch, block_d=bd, interpret=interpret())
    if padded:
        h = h[:, :S, :D]
    return h


def flash_mha(q, k, v, *, causal=True, window=None, block_q=None,
              block_k=None):
    """(B,H,S,d) x (B,K,T,d) flash attention, differentiable; blocks
    default to the shapes' (``flash_blocks``)."""
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret())
