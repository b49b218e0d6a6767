"""Pallas TPU kernel: blocked causal attention with online softmax
("flash attention"), with sliding-window support.

The hot spot for the prefill_32k shape: naive attention materializes the
(S, T) score matrix in HBM (32k x 32k x 4B = 4 GB per head); the blocked
kernel keeps one (bq, bk) tile plus running (m, l, acc) statistics in VMEM —
the MXU sees back-to-back (bq x d)x(d x bk) and (bq x bk)x(bk x d) matmuls.

Grid: (B*H, q_blocks, kv_blocks), kv innermost; scratch carries the online
softmax state across kv steps. Causal/window-masked-out tiles are skipped
with pl.when (grid steps still issue, but do no flops/stores).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, nk: int, scale: float, causal: bool,
                  window):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk
    # tile-level skip: fully above the diagonal, or fully outside the window
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window is not None:
        # newest key this tile offers vs oldest key the oldest query needs
        live &= k_start + bk - 1 >= q_start - window + 1

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)                  # (bk, d)
        s = jnp.dot(q, k.T) * scale                       # (bq, bk)
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kj <= qi
        if window is not None:
            mask &= (qi - kj) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window=None, scale=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q (B,H,S,d), k/v (B,H,T,d) -> (B,H,S,d). Full heads (repeat GQA
    beforehand). d should be MXU-friendly (multiple of 128 ideally)."""
    B, H, S, d = q.shape
    T = k.shape[2]
    assert k.shape == (B, H, T, d) and v.shape == (B, H, T, d)
    bq = min(block_q, S)
    bk = min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    nk = T // bk
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qr = q.reshape(B * H, S, d)
    kr = k.reshape(B * H, T, d)
    vr = v.reshape(B * H, T, d)
    grid = (B * H, S // bq, nk)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk,
                          scale=float(scale), causal=causal, window=window),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
                  pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
                  pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0))],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret, name="flash_attention",
    )(qr, kr, vr)
    return out.reshape(B, H, S, d)
