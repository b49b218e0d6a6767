"""Pallas TPU kernel: fused gossip mix  out = (1-alpha)*local + alpha*recv.

This is GossipGraD's per-step arithmetic (w + w_recv)/2 applied to every
parameter buffer right after the collective-permute delivers the partner's
shard. Fusing it into one VMEM-tiled elementwise kernel avoids materializing
``recv`` round-trips through HBM between the collective and the averaging —
on a 7B-replica gossip step that's ~14 GB of avoided HBM traffic per mix.

Layout: buffers are viewed as (M, LANE) with LANE=128 columns; the grid tiles
rows so each step's working set (3 tiles) fits comfortably in the ~16 MB/core
VMEM budget. The kernel is dtype-native — bf16 buckets are loaded as bf16,
mixed in fp32 on the VPU, and stored back as bf16, so no fp32 scratch copy of
the parameters ever exists. ``gossip_mix_1d`` additionally handles buffers
whose length is not a LANE multiple by mixing the ragged tail (< 128
elements) in a jnp epilogue instead of padding-copying the whole buffer, and
can alias its output onto the local input (``donate=True``) so the mix runs
in place on the persistent gossip buckets.

``alpha`` may be a Python float (baked into the kernel — the PR-1/2 static
path) or a traced fp32 scalar (shipped as a pinned (1, 1) operand every tile
reads). The traced form is the **masked-alpha** path of the bounded-delay
runtime: the staleness-k ring scales alpha by the consumed slot's validity,
so a dropped/late exchange mixes with alpha = 0 — the skip happens inside
the same single sweep, no second pass and no recompiled kernel per mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["gossip_mix_2d", "gossip_mix_q2d", "gossip_mix_1d", "LANE",
           "DEFAULT_ROWS"]

LANE = 128          # TPU lane width
DEFAULT_ROWS = 512  # rows per tile: 512*128*4B*3bufs ~= 786 KB of VMEM


def alpha_is_static(alpha) -> bool:
    """True when ``alpha`` is a Python scalar the kernels can bake in; traced
    values take the masked-alpha operand path."""
    return isinstance(alpha, (int, float))


def _mix_kernel(a_ref, b_ref, o_ref, *, alpha: float):
    # accumulate in fp32 regardless of the buffer dtype (bf16-native wire
    # format, full-precision averaging)
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = (a * (1.0 - alpha) + b * alpha).astype(o_ref.dtype)


def _mix_kernel_dyn(al_ref, a_ref, b_ref, o_ref):
    # masked-alpha variant: alpha arrives as a traced scalar in SMEM — the
    # arithmetic is identical to the static kernel (fp32, same op order), so
    # a traced alpha equal to the static one produces bit-identical output
    al = al_ref[0, 0]
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = (a * (1.0 - al) + b * al).astype(o_ref.dtype)


def gossip_mix_2d(a: jnp.ndarray, b: jnp.ndarray, alpha=0.5,
                  block_rows: int = DEFAULT_ROWS,
                  interpret: bool = False,
                  donate: bool = False) -> jnp.ndarray:
    """a, b: (M, N) with N a multiple of LANE; returns the mixed array.

    ``donate=True`` aliases the output buffer onto ``a`` (in-place mix on the
    persistent bucket — no extra HBM allocation when the caller donates).
    ``alpha``: Python float (static) or traced fp32 scalar (masked-alpha).
    ``b`` may be a narrower dtype than ``a`` (bf16 wire payload mixed into
    an fp32 bucket): both operands are promoted to fp32 in-kernel."""
    assert a.shape == b.shape, (a.shape, b.shape)
    M, N = a.shape
    assert N % LANE == 0, f"last dim {N} must be a multiple of {LANE}"
    bm = min(block_rows, M)
    grid = (pl.cdiv(M, bm),)
    spec = pl.BlockSpec((bm, N), lambda i: (i, 0))
    if alpha_is_static(alpha):
        return pl.pallas_call(
            functools.partial(_mix_kernel, alpha=float(alpha)),
            grid=grid,
            in_specs=[spec, spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
            input_output_aliases={0: 0} if donate else {},
            interpret=interpret, name="gossip_mix",
        )(a, b)
    al = jnp.asarray(alpha, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _mix_kernel_dyn,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        input_output_aliases={1: 0} if donate else {},
        interpret=interpret, name="gossip_mix",
    )(al, a, b)


def _mix_kernel_q(s_ref, a_ref, q_ref, o_ref, *, alpha: float):
    # quantized-wire variant: the partner arrives as int8/fp8 codes plus one
    # fp32 scale per row, decoded in-register — codes.astype(f32) * scale is
    # the exact op the jnp oracle (kernels.quantize.dequant_tiles) runs, so
    # decode-in-kernel and decode-then-mix are bit-identical
    a = a_ref[...].astype(jnp.float32)
    b = q_ref[...].astype(jnp.float32) * s_ref[...]
    o_ref[...] = (a * (1.0 - alpha) + b * alpha).astype(o_ref.dtype)


def _mix_kernel_q_dyn(al_ref, s_ref, a_ref, q_ref, o_ref):
    al = al_ref[0, 0]
    a = a_ref[...].astype(jnp.float32)
    b = q_ref[...].astype(jnp.float32) * s_ref[...]
    o_ref[...] = (a * (1.0 - al) + b * al).astype(o_ref.dtype)


def gossip_mix_q2d(a: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray,
                   alpha=0.5, block_rows: int = DEFAULT_ROWS,
                   interpret: bool = False,
                   donate: bool = False) -> jnp.ndarray:
    """Quantized-wire arrival mix: ``out = (1-alpha)*a + alpha*(q*s)``.

    ``a``: (M, LANE) local bucket view; ``q``: (M, LANE) int8 / fp8 codes;
    ``s``: (M,) or (M, 1) fp32 per-(row, 128)-tile scales, streamed as a
    (bm, 1) column like the LARS trust scale. The decode folds into the
    same single sweep as the mix — the codes never round-trip through HBM
    as fp32. ``alpha`` static or traced (masked-alpha), as in
    ``gossip_mix_2d``."""
    M, N = a.shape
    assert q.shape == (M, N), (a.shape, q.shape)
    assert N == LANE, f"quantized mix operates on (rows, {LANE}) views"
    sc = s.reshape(M, 1).astype(jnp.float32)
    bm = min(block_rows, M)
    grid = (pl.cdiv(M, bm),)
    spec = pl.BlockSpec((bm, N), lambda i: (i, 0))
    s_spec = pl.BlockSpec((bm, 1), lambda i: (i, 0))
    if alpha_is_static(alpha):
        return pl.pallas_call(
            functools.partial(_mix_kernel_q, alpha=float(alpha)),
            grid=grid,
            in_specs=[s_spec, spec, spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
            input_output_aliases={1: 0} if donate else {},
            interpret=interpret, name="gossip_mix_wire",
        )(sc, a, q)
    al = jnp.asarray(alpha, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _mix_kernel_q_dyn,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), s_spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        input_output_aliases={2: 0} if donate else {},
        interpret=interpret, name="gossip_mix_wire",
    )(al, sc, a, q)


def gossip_mix_1d(a: jnp.ndarray, b: jnp.ndarray, alpha=0.5,
                  block_rows: int = DEFAULT_ROWS,
                  interpret: bool = False,
                  donate: bool = False) -> jnp.ndarray:
    """Mix two flat same-shape buffers of ANY length and dtype.

    The LANE-aligned prefix is viewed as (rows, LANE) — a free reshape, not a
    pad copy — and mixed by the tiled kernel; the ragged tail (< LANE
    elements) is mixed by a jnp epilogue. LANE-multiple buffers (the bucket
    invariant) take the pure-kernel path with no tail and no concatenation.
    """
    assert a.shape == b.shape, (a.shape, b.shape)
    n = a.size
    av, bv = a.reshape(-1), b.reshape(-1)
    n_main = (n // LANE) * LANE
    if n_main == n:  # aligned: single kernel call, in-place capable
        out = gossip_mix_2d(av.reshape(-1, LANE), bv.reshape(-1, LANE),
                            alpha=alpha, block_rows=block_rows,
                            interpret=interpret, donate=donate)
        return out.reshape(a.shape)
    parts = []
    if n_main:
        parts.append(gossip_mix_2d(
            av[:n_main].reshape(-1, LANE), bv[:n_main].reshape(-1, LANE),
            alpha=alpha, block_rows=block_rows, interpret=interpret
        ).reshape(-1))
    ta = av[n_main:].astype(jnp.float32)
    tb = bv[n_main:].astype(jnp.float32)
    parts.append((ta * (1.0 - alpha) + tb * alpha).astype(a.dtype))
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return out.reshape(a.shape)
