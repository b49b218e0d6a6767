"""Pallas TPU kernel family: single-sweep fused mix+apply parameter update.

The packed gossip engine's per-step cost after PR 1/2 is pure HBM traffic:
the standalone mix kernel makes one read+write pass over every bucket, then
the tree-level optimizer (``optim/optimizers.py``) makes another 2-3 passes
(read param+grad+moments, write param+moments).  GossipGraD's premise is that
per-step overhead stays O(1) and off the compute path (§5); GoSGD (Blot et
al., 2018) likewise treats the local update and the gossip mix as ONE
combined update.  These kernels do exactly that: a single tiled sweep over a
LANE-aligned bucket that

    1. reads   param + grad + mix_partner + moment(s)          (one pass)
    2. computes the gossip arrival mix  (1-alpha)*p + alpha*partner  in fp32
       — materialized to the bucket dtype in-register, so the result is
       bit-compatible with the standalone ``gossip_mix`` kernel's output —
    3. computes the optimizer update (SGD-momentum / AdamW / LARS) at the
       mixed point, in fp32 regardless of bucket dtype, mirroring the
       tree-level ``Optimizer.update`` formulas op for op, and
    4. writes  param' + moment'(s)                             (one pass),
       with ``input_output_aliases`` donating param and moments onto their
       inputs so the sweep runs in place on the persistent buckets.

``alpha == 0`` (or ``partner is None``) statically drops the partner operand
and its read — the same kernel family serves non-gossip steps (agd / none /
every_logp intermediate steps, dp == 1 smoke meshes) so the train step keeps
one compiled body shape per phase.

``alpha`` may also be a **traced** fp32 scalar (the masked-alpha variant):
it is appended to the coefficient block the kernel already reads (lr, bias
corrections), so the bounded-delay gossip runtime can scale alpha by the
consumed ring slot's validity — a dropped/late exchange dynamically zeroes
the partner term inside the same single sweep (skip-on-timeout), with no
second pass and no per-mask recompilation.  A traced alpha equal to a static
one produces bit-identical output (same fp32 op order).

Aliasing invariants: the param output aliases the param input and each
moment output aliases its moment input (grad and partner are read-only).
Callers must treat the donated inputs as consumed (the packed trainer
donates the whole train state; see tests/test_buckets.py live-buffer
assertions).  ``interpret=True`` skips aliasing (XLA CPU cannot alias).

LARS is not elementwise — its trust ratio needs per-LAYER norms — so it runs
as a two-phase plan: a *norm prepass* (``optim.lars``'s fused backend) reads
the param/grad slices through the same static slot table
``PackedParams.unpack()`` uses and produces one fp32 trust scalar per slot,
expanded to a per-ROW scale vector (slot offsets are LANE-aligned, so every
(row, 128) tile belongs to exactly one slot); the fused kernel then consumes
that (rows, 1) scale as a third read stream (1/128th of a bucket pass).

Every kernel has a ``*_ref`` jnp twin built from the SAME math helpers: the
twin is the test oracle and the CPU fast path (XLA fuses the elementwise
chain into one loop — the single-sweep property without interpret-mode
overhead), while the Pallas kernel is the TPU path.  ``kernels.ops`` picks
per backend.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .gossip_mix import alpha_is_static as _alpha_static

__all__ = [
    "LANE", "DEFAULT_ROWS",
    "fused_sgd_1d", "fused_adamw_1d", "fused_lars_1d",
    "fused_sgd_ref", "fused_adamw_ref", "fused_lars_ref",
]

LANE = 128          # TPU lane width
DEFAULT_ROWS = 256  # rows/tile: 256*128*4B*6bufs ~= 786 KB of VMEM


# --------------------------------------------------------------- shared math
# One definition of the update arithmetic, used by BOTH the Pallas kernel
# bodies and the jnp reference twins, so the two paths are bit-identical and
# both mirror optim/optimizers.py op for op.

def _mix_f32(p32: jnp.ndarray, partner: Optional[jnp.ndarray], alpha,
             store_dtype, partner_scale=None) -> jnp.ndarray:
    """Arrival mix in fp32; round-trips through the bucket dtype so the
    fused path is bit-compatible with the standalone mix kernel's output
    (which materializes ``mixed`` in the bucket dtype). ``alpha`` may be a
    Python float or a traced fp32 scalar (masked-alpha).

    ``partner_scale`` (quantized wire): the partner operand is int8/fp8
    CODES and ``partner_scale`` the per-(row, 128)-tile fp32 scale — the
    decode ``codes.astype(f32) * scale`` folds into this same sweep and is
    bit-identical to the jnp oracle's ``dequant_tiles`` (same op, same
    order)."""
    if partner is None or (_alpha_static(alpha) and alpha == 0.0):
        return p32
    b32 = partner.astype(jnp.float32)
    if partner_scale is not None:
        b32 = b32 * partner_scale
    mixed = p32 * (1.0 - alpha) + b32 * alpha
    return mixed.astype(store_dtype).astype(jnp.float32)


def _sgd_math(p32, g32, m32, lr, *, momentum: float, weight_decay: float):
    """Mirrors optim.sgd.update: wd folds into the grad BEFORE momentum."""
    if weight_decay:
        g32 = g32 + weight_decay * p32
    if m32 is None:
        return p32 - lr * g32, None
    m32 = momentum * m32 + g32
    return p32 - lr * m32, m32


def _adamw_math(p32, g32, m32, v32, lr, c1, c2, *, b1: float, b2: float,
                eps: float, weight_decay: float):
    """Mirrors optim.adamw.update; c1/c2 are the bias corrections computed
    from the NEW step count (a scalar input, like lr)."""
    m32 = b1 * m32 + (1 - b1) * g32
    v32 = b2 * v32 + (1 - b2) * jnp.square(g32)
    u = (m32 / c1) / (jnp.sqrt(v32 / c2) + eps)
    if weight_decay:
        u = u + weight_decay * p32
    return p32 - lr * u, m32, v32


def _lars_math(p32, g32, m32, scale, lr, *, momentum: float,
               weight_decay: float):
    """Mirrors optim.lars.update's per-leaf body with the trust ratio
    precomputed (``scale`` broadcasts per row)."""
    if weight_decay:
        g32 = g32 + weight_decay * p32
    m32 = momentum * m32 + g32 * scale
    return p32 - lr * m32, m32


# ------------------------------------------------------------ kernel bodies
# Ref layout: coef (1, k) fp32 scalars | [scale (bm, 1)] | param (bm, LANE) |
# grad | [partner] | moments...  ->  param' (bm, LANE) | moments'...
# ``alpha=None`` in a body means the masked-alpha variant: alpha rides as
# the LAST coefficient in the coef block (its width is static, so the index
# resolves at trace time). ``has_pscale`` prepends a (bm, 1) per-row wire
# scale column (quantized partner decode, see kernels.quantize): the partner
# ref then holds int8/fp8 codes, decoded in-register via ``_mix_f32``'s
# ``partner_scale``.

def _body_alpha(coef_ref, alpha):
    return coef_ref[0, coef_ref.shape[-1] - 1] if alpha is None else alpha


def _sgd_kernel(coef_ref, *all_refs, alpha, momentum, weight_decay,
                has_partner, has_mom, has_pscale=False):
    refs = list(all_refs)
    ps_ref = refs.pop(0) if has_pscale else None
    p_ref = refs.pop(0)
    g_ref = refs.pop(0)
    b_ref = refs.pop(0) if has_partner else None
    m_ref = refs.pop(0) if has_mom else None
    po_ref = refs.pop(0)
    mo_ref = refs.pop(0) if has_mom else None
    lr = coef_ref[0, 0]
    p = _mix_f32(p_ref[...].astype(jnp.float32),
                 b_ref[...] if b_ref is not None else None,
                 _body_alpha(coef_ref, alpha), po_ref.dtype,
                 partner_scale=ps_ref[...] if ps_ref is not None else None)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32) if has_mom else None
    p, m = _sgd_math(p, g, m, lr, momentum=momentum,
                     weight_decay=weight_decay)
    po_ref[...] = p.astype(po_ref.dtype)
    if has_mom:
        mo_ref[...] = m.astype(mo_ref.dtype)


def _adamw_kernel(coef_ref, *all_refs, alpha, b1, b2, eps,
                  weight_decay, has_partner, has_pscale=False):
    refs = list(all_refs)
    ps_ref = refs.pop(0) if has_pscale else None
    p_ref = refs.pop(0)
    g_ref = refs.pop(0)
    b_ref = refs.pop(0) if has_partner else None
    m_ref, v_ref, po_ref, mo_ref, vo_ref = refs
    lr, c1, c2 = coef_ref[0, 0], coef_ref[0, 1], coef_ref[0, 2]
    p = _mix_f32(p_ref[...].astype(jnp.float32),
                 b_ref[...] if b_ref is not None else None,
                 _body_alpha(coef_ref, alpha), po_ref.dtype,
                 partner_scale=ps_ref[...] if ps_ref is not None else None)
    g = g_ref[...].astype(jnp.float32)
    p, m, v = _adamw_math(p, g, m_ref[...], v_ref[...], lr, c1, c2,
                          b1=b1, b2=b2, eps=eps, weight_decay=weight_decay)
    po_ref[...] = p.astype(po_ref.dtype)
    mo_ref[...] = m
    vo_ref[...] = v


def _lars_kernel(coef_ref, s_ref, p_ref, g_ref, *refs, alpha, momentum,
                 weight_decay, has_partner):
    refs = list(refs)
    b_ref = refs.pop(0) if has_partner else None
    m_ref, po_ref, mo_ref = refs
    lr = coef_ref[0, 0]
    p = _mix_f32(p_ref[...].astype(jnp.float32),
                 b_ref[...] if b_ref is not None else None,
                 _body_alpha(coef_ref, alpha), po_ref.dtype)
    g = g_ref[...].astype(jnp.float32)
    p, m = _lars_math(p, g, m_ref[...], s_ref[...], lr, momentum=momentum,
                      weight_decay=weight_decay)
    po_ref[...] = p.astype(po_ref.dtype)
    mo_ref[...] = m


# ------------------------------------------------------------- tiled caller

def _tiled_call(body, coefs, col_ins, lane_ins, out_dtypes, aliases, *,
                block_rows: int, interpret: bool, donate: bool):
    """Grid-tile ``body`` over (M, LANE) views.

    ``coefs``: traced fp32 scalars, shipped as one (1, k) block every tile
    reads (index_map pins it to the origin — SMEM-sized, never re-fetched).
    ``col_ins``: (M, 1) per-row streams (the LARS trust scale).
    ``lane_ins``: (M, LANE) streams — param, grad, partner, moments.
    ``aliases``: {lane_input_position: output_position} donation map
    (positions are within ``lane_ins`` / the output tuple).
    """
    M = lane_ins[0].shape[0]
    bm = min(block_rows, M)
    grid = (pl.cdiv(M, bm),)
    coef = jnp.stack([jnp.asarray(c, jnp.float32) for c in coefs])[None, :]
    in_specs = [pl.BlockSpec((1, len(coefs)), lambda i: (0, 0))]
    in_specs += [pl.BlockSpec((bm, 1), lambda i: (i, 0)) for _ in col_ins]
    in_specs += [pl.BlockSpec((bm, LANE), lambda i: (i, 0)) for _ in lane_ins]
    out_specs = [pl.BlockSpec((bm, LANE), lambda i: (i, 0)) for _ in out_dtypes]
    base = 1 + len(col_ins)  # coef + col streams precede the lane streams
    io_aliases = {base + k: v for k, v in aliases.items()} if donate else {}
    out = pl.pallas_call(
        body, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((M, LANE), dt) for dt in out_dtypes],
        input_output_aliases=io_aliases, interpret=interpret,
        name="fused_update",
    )(coef, *col_ins, *lane_ins)
    return tuple(out)


def _split_aligned(arrs):
    """(aligned (M, LANE) views, ragged tails) of same-size arrays. A
    LANE-multiple array, such as a packed bucket stored as ``(..., M,
    LANE)``, is viewed whole: a bitcast, never a slice or a copy."""
    n = arrs[0].size
    if n % LANE == 0:
        return [a.reshape(-1, LANE) for a in arrs], None
    n_main = (n // LANE) * LANE
    mains = [a.reshape(-1)[:n_main].reshape(-1, LANE) for a in arrs]
    tails = [a.reshape(-1)[n_main:] for a in arrs] if n_main != n else None
    return mains, tails


def _join(main, tail, shape, dtype):
    flat = main.reshape(-1)
    if tail is not None:
        flat = jnp.concatenate([flat, tail.astype(dtype)])
    return flat.reshape(shape)


# ----------------------------------------------------------- public: pallas

def fused_sgd_1d(p, g, partner, mom, *, lr, alpha=0.5, momentum=0.9,
                 weight_decay=0.0, partner_scales=None,
                 block_rows=DEFAULT_ROWS, interpret=False,
                 donate=False) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Fused mix+SGD over a buffer of any length/leading shape: a packed
    bucket's ``(..., rows, 128)`` storage, or a flat buffer.

    The LANE-aligned prefix runs through the tiled kernel (aliasing param and
    momentum outputs onto their inputs when ``donate``); a ragged tail
    (< LANE elements) is updated by a jnp epilogue built from the same math.
    ``partner=None`` or static ``alpha=0`` drops the mix operand; a traced
    ``alpha`` rides the coefficient block (masked-alpha variant).
    ``partner_scales`` (fp32, one per (row, 128) tile) marks ``partner`` as
    quantized wire codes, decoded in-kernel via a (bm, 1) scale column —
    LANE-aligned buffers only (the bucket invariant).
    """
    dyn = not _alpha_static(alpha)
    has_partner = partner is not None and (dyn or alpha != 0.0)
    has_mom = mom is not None
    has_pscale = has_partner and partner_scales is not None
    if has_pscale:
        assert p.size % LANE == 0, \
            f"quantized partner needs LANE-aligned buffers, got {p.shape}"
        assert partner_scales.size == p.size // LANE, \
            (partner_scales.shape, p.shape)
    body = functools.partial(_sgd_kernel,
                             alpha=None if dyn else float(alpha),
                             momentum=float(momentum),
                             weight_decay=float(weight_decay),
                             has_partner=has_partner, has_mom=has_mom,
                             has_pscale=has_pscale)
    ins = [p, g] + ([partner] if has_partner else []) \
        + ([mom] if has_mom else [])
    mains, tails = _split_aligned(ins)
    col_ins = [partner_scales.reshape(-1, 1).astype(jnp.float32)] \
        if has_pscale else []
    outs = ([p.dtype, mom.dtype] if has_mom else [p.dtype])
    aliases = {0: 0, len(mains) - 1: 1} if has_mom else {0: 0}
    coefs = [lr] + ([alpha] if dyn else [])
    if mains[0].shape[0]:
        ko = _tiled_call(body, coefs, col_ins, mains, outs, aliases,
                         block_rows=block_rows, interpret=interpret,
                         donate=donate)
    else:
        ko = tuple(jnp.zeros((0, LANE), dt) for dt in outs)
    tp = tm = None
    if tails is not None:
        t = tails
        pf = _mix_f32(t[0].astype(jnp.float32), t[2] if has_partner else None,
                      alpha, p.dtype)
        mf = t[-1].astype(jnp.float32) if has_mom else None
        tp, tm = _sgd_math(pf, t[1].astype(jnp.float32), mf, lr,
                           momentum=momentum, weight_decay=weight_decay)
    new_p = _join(ko[0], tp, p.shape, p.dtype)
    new_m = _join(ko[1], tm, mom.shape, mom.dtype) if has_mom else None
    return new_p, new_m


def fused_adamw_1d(p, g, partner, m, v, *, lr, c1, c2, alpha=0.5, b1=0.9,
                   b2=0.95, eps=1e-8, weight_decay=0.0, partner_scales=None,
                   block_rows=DEFAULT_ROWS, interpret=False, donate=False):
    """Fused mix+AdamW; ``c1``/``c2`` are the (1 - beta^t) bias corrections
    of the NEW step count (scalars, like ``lr``). A traced ``alpha`` rides
    the coefficient block (masked-alpha variant); ``partner_scales`` marks
    ``partner`` as quantized wire codes (see ``fused_sgd_1d``)."""
    dyn = not _alpha_static(alpha)
    has_partner = partner is not None and (dyn or alpha != 0.0)
    has_pscale = has_partner and partner_scales is not None
    if has_pscale:
        assert p.size % LANE == 0, \
            f"quantized partner needs LANE-aligned buffers, got {p.shape}"
        assert partner_scales.size == p.size // LANE, \
            (partner_scales.shape, p.shape)
    body = functools.partial(_adamw_kernel,
                             alpha=None if dyn else float(alpha),
                             b1=float(b1), b2=float(b2), eps=float(eps),
                             weight_decay=float(weight_decay),
                             has_partner=has_partner, has_pscale=has_pscale)
    ins = [p, g] + ([partner] if has_partner else []) + [m, v]
    mains, tails = _split_aligned(ins)
    col_ins = [partner_scales.reshape(-1, 1).astype(jnp.float32)] \
        if has_pscale else []
    nin = len(mains)
    aliases = {0: 0, nin - 2: 1, nin - 1: 2}
    coefs = [lr, c1, c2] + ([alpha] if dyn else [])
    if mains[0].shape[0]:
        ko = _tiled_call(body, coefs, col_ins, mains,
                         [p.dtype, jnp.float32, jnp.float32], aliases,
                         block_rows=block_rows, interpret=interpret,
                         donate=donate)
    else:
        ko = (jnp.zeros((0, LANE), p.dtype),) + \
            tuple(jnp.zeros((0, LANE), jnp.float32) for _ in range(2))
    tp = tm = tv = None
    if tails is not None:
        t = tails
        pf = _mix_f32(t[0].astype(jnp.float32), t[2] if has_partner else None,
                      alpha, p.dtype)
        tp, tm, tv = _adamw_math(pf, t[1].astype(jnp.float32),
                                 t[-2].astype(jnp.float32),
                                 t[-1].astype(jnp.float32), lr, c1, c2,
                                 b1=b1, b2=b2, eps=eps,
                                 weight_decay=weight_decay)
    return (_join(ko[0], tp, p.shape, p.dtype),
            _join(ko[1], tm, m.shape, jnp.float32),
            _join(ko[2], tv, v.shape, jnp.float32))


def fused_lars_1d(p, g, partner, mom, row_scale, *, lr, alpha=0.5,
                  momentum=0.9, weight_decay=0.0, block_rows=DEFAULT_ROWS,
                  interpret=False, donate=False):
    """Fused mix+LARS with the per-row trust scale from the norm prepass.

    ``row_scale``: fp32 of shape (p.size // LANE,) — one trust ratio per
    (row, 128) tile (slot offsets are LANE-aligned, so a row never spans two
    layers).  LANE-aligned buffers only (the bucket invariant).
    """
    assert p.size % LANE == 0, f"lars fused path needs LANE-aligned buffers, got {p.shape}"
    assert row_scale.size == p.size // LANE, (row_scale.shape, p.shape)
    dyn = not _alpha_static(alpha)
    has_partner = partner is not None and (dyn or alpha != 0.0)
    body = functools.partial(_lars_kernel,
                             alpha=None if dyn else float(alpha),
                             momentum=float(momentum),
                             weight_decay=float(weight_decay),
                             has_partner=has_partner)
    ins = [p, g] + ([partner] if has_partner else []) + [mom]
    mains, _ = _split_aligned(ins)
    scale = row_scale.reshape(-1, 1).astype(jnp.float32)
    nin = len(mains)
    coefs = [lr] + ([alpha] if dyn else [])
    ko = _tiled_call(body, coefs, [scale], mains, [p.dtype, jnp.float32],
                     {0: 0, nin - 1: 1}, block_rows=block_rows,
                     interpret=interpret, donate=donate)
    return (ko[0].reshape(p.shape),
            ko[1].reshape(mom.shape).astype(jnp.float32))


# ------------------------------------------------------- public: jnp twins
# Same math helpers, evaluated as one jnp elementwise chain: XLA fuses it
# into a single loop over the bucket (the CPU fast path) and it doubles as
# the bit-exact oracle for the Pallas kernels.  Like the kernels, ``alpha``
# may be a Python float or a traced fp32 scalar (masked-alpha).

def _ref_partner(partner, alpha):
    return partner if (partner is not None
                       and not (_alpha_static(alpha) and alpha == 0.0)) \
        else None


def fused_sgd_ref(p, g, partner, mom, *, lr, alpha=0.5, momentum=0.9,
                  weight_decay=0.0):
    pf = _mix_f32(p.astype(jnp.float32), _ref_partner(partner, alpha),
                  alpha, p.dtype)
    mf = mom.astype(jnp.float32) if mom is not None else None
    np_, nm = _sgd_math(pf, g.astype(jnp.float32), mf, lr, momentum=momentum,
                        weight_decay=weight_decay)
    return (np_.astype(p.dtype),
            nm.astype(mom.dtype) if mom is not None else None)


def fused_adamw_ref(p, g, partner, m, v, *, lr, c1, c2, alpha=0.5, b1=0.9,
                    b2=0.95, eps=1e-8, weight_decay=0.0):
    pf = _mix_f32(p.astype(jnp.float32), _ref_partner(partner, alpha),
                  alpha, p.dtype)
    np_, nm, nv = _adamw_math(pf, g.astype(jnp.float32), m.astype(jnp.float32),
                              v.astype(jnp.float32), lr, c1, c2, b1=b1, b2=b2,
                              eps=eps, weight_decay=weight_decay)
    return np_.astype(p.dtype), nm, nv


def fused_lars_ref(p, g, partner, mom, row_scale, *, lr, alpha=0.5,
                   momentum=0.9, weight_decay=0.0):
    assert p.size % LANE == 0, p.shape
    # (rows, LANE) views with the trust scale as a (rows, 1) column, as the
    # kernel sees them: the same broadcast gives XLA the same FMA
    # contraction of momentum*m + g*scale, so twin and kernel agree bitwise
    rows = lambda x: x.reshape(-1, LANE)
    partner = _ref_partner(partner, alpha)
    pf = _mix_f32(rows(p).astype(jnp.float32),
                  rows(partner) if partner is not None else None,
                  alpha, p.dtype)
    scale = row_scale.reshape(-1, 1).astype(jnp.float32)
    np_, nm = _lars_math(pf, rows(g).astype(jnp.float32),
                         rows(mom).astype(jnp.float32), scale, lr,
                         momentum=momentum, weight_decay=weight_decay)
    return np_.reshape(p.shape).astype(p.dtype), nm.reshape(mom.shape)
