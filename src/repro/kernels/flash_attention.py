"""Pallas TPU kernels: blocked causal attention with online softmax
("flash attention"), forward and backward, with sliding windows and native
grouped-query heads.

Naive attention materializes the (S, T) score and probability tensors in
HBM, and autodiff writes and reads them again in the backward. These
kernels keep one (bq, bk) tile in VMEM: the MXU sees back-to-back
(bq x d)x(d x bk) and (bq x bk)x(bk x d) products, and HBM holds only q, k,
v, the output and one f32 log-sum-exp per query row.

Layout: q (B, H, S, d), k/v (B, K, T, d) with H a multiple of K; query head
h reads KV head h // (H // K) through the K/V index maps, so grouped KV is
never repeated in HBM and dK/dV come out at (B, K, T, d), summed over the
group.

* forward (``flash_attention``): grid (B, H, S/bq, T/bk), kv innermost;
  scratch carries the running max, sum and accumulator across kv steps and
  the last step writes o and lse = m + log(l).
* dK/dV (``flash_attention_dkv``): grid (B, K, T/bk, G, S/bq); for one KV
  block it walks the G query heads of its group and their q blocks,
  accumulating dK and dV in f32 VMEM. It works on transposed tiles
  (keys x queries) so lse and D = rowsum(dO * o) are read as lane-dense rows.
* dQ (``flash_attention_dq``): grid (B, H, S/bq, T/bk), kv innermost,
  accumulating dQ in f32 VMEM.

Both backward kernels walk each block in ``SUB_TILE``-square tiles and skip
those wholly above the diagonal, so the diagonal block costs little more
than its live half.

Precision: dot operands stay in the input dtype, every product accumulates
in f32, scores and softmax statistics are f32 with the scale applied in f32,
and P is cast to the V dtype for its products. Tiles wholly above the causal
diagonal or outside the window are skipped with ``pl.when``: their
probabilities are exact zeros. Their index maps repeat the previous live
block, so a skipped step fetches nothing new. Only tiles the diagonal or the
window edge crosses build a mask.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_blocks"]

NEG_INF = -1e30
SUB_TILE = 256
_NT = (((1,), (1,)), ((), ()))          # a @ b.T


def flash_blocks(S: int, T: int, head_dim: int) -> Optional[Tuple[int, int]]:
    """(bq, bk) for a (S, T) attention at ``head_dim``, or None where the
    kernels cannot tile it: the largest of 1024, 512, 256 and 128 that
    divides each length (512 at most above head_dim 128, where a tile's q
    and k blocks double). Fewer, larger grid steps win over skipping more
    of the causal triangle: at S 1024, head_dim 128 on a v5e one 1024 x
    1024 tile per head beat 512 x 512 tiles and 256 x 256 ones. 128 is the
    lane width every row of lse and every transposed tile needs."""
    cap = 1024 if head_dim <= 128 else 512
    pick = lambda n: next((b for b in (1024, 512, 256, 128)
                           if b <= cap and n % b == 0), None)
    bq, bk = pick(S), pick(T)
    return None if bq is None or bk is None else (bq, bk)


class _Cfg(NamedTuple):
    causal: bool
    window: Optional[int]
    scale: float
    bq: int
    bk: int
    sub: int            # edge of the square tiles the backward walks
    interpret: bool


# ------------------------------------------------------------- tile logic
def _live(cfg: _Cfg, q0, k0):
    """Does the (q0.., k0..) tile hold any unmasked score? A Python bool
    where q0 and k0 are Python ints."""
    live = True
    if cfg.causal:
        live &= k0 <= q0 + cfg.bq - 1
    if cfg.window is not None:
        # newest key of the tile vs oldest key the oldest query needs
        live &= k0 + cfg.bk - 1 >= q0 - cfg.window + 1
    return live


def _edge(cfg: _Cfg, q0, k0):
    """Does the diagonal or the window's edge cross the tile?"""
    edge = False
    if cfg.causal:
        edge |= k0 + cfg.bk - 1 > q0
    if cfg.window is not None:
        edge |= q0 + cfg.bq - 1 - k0 >= cfg.window
    return edge


def _mask(cfg: _Cfg, q0, k0, shape, q_axis: int):
    """Bool mask of the tile's unmasked scores; queries along ``q_axis``."""
    qi = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kj = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    keep = jnp.ones(shape, jnp.bool_)
    if cfg.causal:
        keep &= kj <= qi
    if cfg.window is not None:
        keep &= qi - kj < cfg.window
    return keep


def _not(x):
    return (not x) if isinstance(x, bool) else jnp.logical_not(x)


def _tiles(cfg: _Cfg, q0, k0, body):
    """Run ``body(masked)`` on a live tile: masked only where an edge
    crosses it. Where the tile's place is static, so is the choice."""
    if not (cfg.causal or cfg.window is not None):
        body(False)
        return
    live, edge = _live(cfg, q0, k0), _edge(cfg, q0, k0)
    pl.when(live & _not(edge))(lambda: body(False))
    pl.when(live & edge)(lambda: body(True))


def _program_id(axis: int, n: int):
    """The grid index along ``axis``, a static 0 where the axis has one
    step (the tile logic then resolves while tracing)."""
    return pl.program_id(axis) if n > 1 else 0


def _subtiles(cfg: _Cfg, q0, k0, body, kv_outer: bool = False):
    """Walk the (bq, bk) block at (q0, k0) in (sub, sub) tiles, running
    ``body(masked, a, c)`` on each live one: q rows a*sub.., kv rows
    c*sub.. of the block. Only tiles the diagonal or the window's edge
    crosses are masked, and a block the diagonal crosses skips the tiles
    wholly above it. The backward kernels, which run near the MXU's peak
    on what they compute, walk their blocks so; the forward, whose online
    softmax pays for every tile, takes each block whole."""
    sq, sk = min(cfg.sub, cfg.bq), min(cfg.sub, cfg.bk)
    tile = cfg._replace(bq=sq, bk=sk)
    pairs = [(a, c) for a in range(cfg.bq // sq) for c in range(cfg.bk // sk)]
    if kv_outer:
        pairs.sort(key=lambda ac: (ac[1], ac[0]))
    for a, c in pairs:
        _tiles(tile, q0 + a * sq, k0 + c * sk,
               functools.partial(body, a=a, c=c))


def _kv_block(cfg: _Cfg, i, j):
    """KV block to fetch at q block i, kv step j: j clamped to the live
    range, so a skipped step repeats a fetched block."""
    if cfg.causal:
        j = jnp.minimum(j, (i * cfg.bq + cfg.bq - 1) // cfg.bk)
    if cfg.window is not None:
        j = jnp.maximum(j, jnp.maximum(i * cfg.bq - cfg.window + 1, 0)
                        // cfg.bk)
    return j


def _q_block(cfg: _Cfg, j, i, nq: int):
    """Q block to fetch at kv block j, q step i, clamped alike."""
    if cfg.causal:
        i = jnp.maximum(i, (j * cfg.bk) // cfg.bq)
    if cfg.window is not None:
        last = (j * cfg.bk + cfg.bk + cfg.window - 2) // cfg.bq
        i = jnp.minimum(i, jnp.minimum(last, nq - 1))
    return i


def _row_to_col(row):
    """(1, n) -> (n, 1) through a lane-aligned 2-D transpose."""
    return jnp.transpose(jnp.broadcast_to(row, (128, row.shape[1])))[:, :1]


def _col_to_row(col):
    """(n, 1) -> (1, n), the inverse of ``_row_to_col``."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], 128)))[:1, :]


def _params(n_parallel: int, n_arbitrary: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel
        + ("arbitrary",) * n_arbitrary)


# ---------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                cfg: _Cfg, nq: int, nk: int):
    i, j = _program_id(2, nq), _program_id(3, nk)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q0, k0 = i * cfg.bq, j * cfg.bk

    def body(masked: bool):
        v = v_ref[...]
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                preferred_element_type=jnp.float32)
        s = s * cfg.scale
        if masked:
            s = jnp.where(_mask(cfg, q0, k0, s.shape, 0), s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    _tiles(cfg, q0, k0, body)

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[...] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[...] = _col_to_row(m_sc[...] + jnp.log(l))


def _fwd(q, k, v, cfg: _Cfg):
    B, H, S, d = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    nq, nk = S // cfg.bq, T // cfg.bk
    kv = lambda b, h, i, j: (b, h // G, _kv_block(cfg, i, j), 0)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg, nq=nq, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[pl.BlockSpec((None, None, cfg.bq, d),
                               lambda b, h, i, j: (b, h, i, 0)),
                  pl.BlockSpec((None, None, cfg.bk, d), kv),
                  pl.BlockSpec((None, None, cfg.bk, d), kv)],
        out_specs=[pl.BlockSpec((None, None, cfg.bq, d),
                                lambda b, h, i, j: (b, h, i, 0)),
                   pl.BlockSpec((None, None, 1, cfg.bq),
                                lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, d), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((cfg.bq, 1), jnp.float32),
                        pltpu.VMEM((cfg.bq, 1), jnp.float32),
                        pltpu.VMEM((cfg.bq, d), jnp.float32)],
        compiler_params=_params(3, 1),
        interpret=cfg.interpret, name="flash_attention",
    )(q, k, v)


# --------------------------------------------------------------- backward
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, cfg: _Cfg, G: int, nq: int, nk: int):
    j, g, i = _program_id(2, nk), _program_id(3, G), _program_id(4, nq)

    @pl.when((g == 0) & (i == 0))
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    sq, sk = min(cfg.sub, cfg.bq), min(cfg.sub, cfg.bk)

    def body(masked: bool, a: int, c: int):
        rows, cols = pl.ds(a * sq, sq), pl.ds(c * sk, sk)
        q, do, v = q_ref[rows, :], do_ref[rows, :], v_ref[cols, :]
        # transposed tile: keys down, queries across
        s = jax.lax.dot_general(k_ref[cols, :], q, _NT,
                                preferred_element_type=jnp.float32)
        s = s * cfg.scale
        if masked:
            s = jnp.where(_mask(cfg, i * cfg.bq + a * sq,
                                j * cfg.bk + c * sk, s.shape, 1), s, NEG_INF)
        p = jnp.exp(s - lse_ref[:, rows])
        dv_sc[cols, :] += jnp.dot(p.astype(v.dtype), do,
                                  preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[:, rows])
        dk_sc[cols, :] += jnp.dot(ds.astype(q.dtype), q,
                                  preferred_element_type=jnp.float32)

    _subtiles(cfg, i * cfg.bq, j * cfg.bk, body, kv_outer=True)

    @pl.when((g == G - 1) & (i == nq - 1))
    def _finish():
        dk_ref[...] = (dk_sc[...] * cfg.scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               dq_sc, lse_sc, di_sc, *, cfg: _Cfg, nq: int, nk: int):
    i, j = _program_id(2, nq), _program_id(3, nk)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)
        lse_sc[...] = _row_to_col(lse_ref[...])
        di_sc[...] = _row_to_col(di_ref[...])

    sq, sk = min(cfg.sub, cfg.bq), min(cfg.sub, cfg.bk)

    def body(masked: bool, a: int, c: int):
        rows, cols = pl.ds(a * sq, sq), pl.ds(c * sk, sk)
        k = k_ref[cols, :]
        s = jax.lax.dot_general(q_ref[rows, :], k, _NT,
                                preferred_element_type=jnp.float32)
        s = s * cfg.scale
        if masked:
            s = jnp.where(_mask(cfg, i * cfg.bq + a * sq,
                                j * cfg.bk + c * sk, s.shape, 0), s, NEG_INF)
        p = jnp.exp(s - lse_sc[rows, :])
        dp = jax.lax.dot_general(do_ref[rows, :], v_ref[cols, :], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_sc[rows, :])
        dq_sc[rows, :] += jnp.dot(ds.astype(k.dtype), k,
                                  preferred_element_type=jnp.float32)

    _subtiles(cfg, i * cfg.bq, j * cfg.bk, body)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[...] = (dq_sc[...] * cfg.scale).astype(dq_ref.dtype)


def _bwd_calls(q, k, v, do, lse, di, cfg: _Cfg):
    B, H, S, d = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    nq, nk = S // cfg.bq, T // cfg.bk
    bq, bk = cfg.bq, cfg.bk

    # dK/dV: KV head kh, kv block j; walk query heads kh*G+g and q blocks i
    qmap = lambda b, kh, j, g, i: (b, kh * G + g, _q_block(cfg, j, i, nq), 0)
    rowmap = lambda b, kh, j, g, i: (b, kh * G + g, 0,
                                     _q_block(cfg, j, i, nq))
    kvmap = lambda b, kh, j, g, i: (b, kh, j, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg=cfg, G=G, nq=nq, nk=nk),
        grid=(B, K, nk, G, nq),
        in_specs=[pl.BlockSpec((None, None, bq, d), qmap),
                  pl.BlockSpec((None, None, bk, d), kvmap),
                  pl.BlockSpec((None, None, bk, d), kvmap),
                  pl.BlockSpec((None, None, bq, d), qmap),
                  pl.BlockSpec((None, None, 1, bq), rowmap),
                  pl.BlockSpec((None, None, 1, bq), rowmap)],
        out_specs=[pl.BlockSpec((None, None, bk, d), kvmap),
                   pl.BlockSpec((None, None, bk, d), kvmap)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_params(3, 2),
        interpret=cfg.interpret, name="flash_attention_dkv",
    )(q, k, v, do, lse, di)

    # dQ: query head h, q block i; walk kv blocks j
    kv = lambda b, h, i, j: (b, h // G, _kv_block(cfg, i, j), 0)
    own = lambda b, h, i, j: (b, h, i, 0)
    row = lambda b, h, i, j: (b, h, 0, i)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg=cfg, nq=nq, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[pl.BlockSpec((None, None, bq, d), own),
                  pl.BlockSpec((None, None, bk, d), kv),
                  pl.BlockSpec((None, None, bk, d), kv),
                  pl.BlockSpec((None, None, bq, d), own),
                  pl.BlockSpec((None, None, 1, bq), row),
                  pl.BlockSpec((None, None, 1, bq), row)],
        out_specs=pl.BlockSpec((None, None, bq, d), own),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=_params(3, 1),
        interpret=cfg.interpret, name="flash_attention_dq",
    )(q, k, v, do, lse, di)
    return dq, dk, dv


# ------------------------------------------------------------ custom_vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg: _Cfg):
    return _fwd(q, k, v, cfg)[0]


def _flash_fwd(q, k, v, cfg: _Cfg):
    o, lse = _fwd(q, k, v, cfg)
    return o, (q, k, v, o, lse)


def _flash_bwd(cfg: _Cfg, res, do):
    q, k, v, o, lse = res
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return _bwd_calls(q, k, v, do, lse, di[:, :, None], cfg)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window=None, scale=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    block_sub: int = SUB_TILE,
                    interpret: bool = False) -> jnp.ndarray:
    """q (B,H,S,d), k/v (B,K,T,d) with H % K == 0 -> (B,H,S,d),
    differentiable in q, k and v. Blocks default to ``flash_blocks``; an
    explicit block larger than its length is cut to it. The backward walks
    each block in ``block_sub``-square tiles."""
    B, H, S, d = q.shape
    K, T = k.shape[1], k.shape[2]
    assert k.shape == (B, K, T, d) and v.shape == k.shape, (q.shape, k.shape)
    assert H % K == 0, (H, K)
    if block_q is None or block_k is None:
        blocks = flash_blocks(S, T, d)
        assert blocks is not None, f"no flash blocks for S={S}, T={T}"
        block_q = block_q or blocks[0]
        block_k = block_k or blocks[1]
    bq, bk = min(block_q, S), min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    sub = min(block_sub, bq, bk)
    assert bq % sub == 0 and bk % sub == 0, (bq, bk, sub)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    cfg = _Cfg(causal=bool(causal), window=window, scale=float(scale),
               bq=bq, bk=bk, sub=sub, interpret=bool(interpret))
    return _flash(q, k, v, cfg)
