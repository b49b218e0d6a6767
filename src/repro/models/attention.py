"""Attention mixers: MHA/GQA (+qk_norm, partial rotary, sliding window),
cross-attention (enc-dec), and DeepSeek-V3 MLA with absorbed-latent decode.

Train path operates on a full sequence with a causal (optionally windowed)
mask; decode path consumes ONE new token against a KV cache:

* full attention      — cache (B, S_cache, Kv, hd), written at ``pos``;
* sliding window      — ring-buffer cache (B, W, Kv, hd), written at
                        ``pos % W`` (memory O(window), the sub-quadratic
                        variant that makes long_500k feasible for dense archs);
* MLA                 — latent cache (B, S_cache, kv_lora + rope_dim): decode
                        absorbs the kv up-projection into the query/output so
                        attention runs in the compressed latent space.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist_ctx import constrain_logical, current_distribution
from repro.kernels.flash_attention import flash_attention, flash_blocks
from .config import AttnSpec, MLASpec
from .layers import Param, dense_param, norm_apply
from .rotary import apply_rope, rope_frequencies

PyTree = Any
NEG_INF = -1e30
_PATH_COUNTS: list = []     # open count_attn_paths() scopes, innermost last

__all__ = [
    "attn_init", "attn_apply", "attn_decode", "attn_cache_init",
    "count_attn_paths",
    "mla_init", "mla_apply", "mla_decode", "mla_cache_init", "cache_len",
]


def cache_len(seq_len: int, window: Optional[int]) -> int:
    """Physical KV-cache length: ring buffer of ``window`` if windowed."""
    return seq_len if window is None else min(seq_len, window)


# ===================================================================== GQA
def attn_init(key, d_model: int, spec: AttnSpec, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    H, K, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    assert H % K == 0, (H, K)
    p, a = {}, {}
    p["wq"], a["wq"] = dense_param(ks[0], d_model, (H, hd), "embed", ("heads", "head_dim"), dtype=dtype)
    p["wk"], a["wk"] = dense_param(ks[1], d_model, (K, hd), "embed", ("kv_heads", "head_dim"), dtype=dtype)
    p["wv"], a["wv"] = dense_param(ks[2], d_model, (K, hd), "embed", ("kv_heads", "head_dim"), dtype=dtype)
    p["wo"], a["wo"] = Param(ks[3], (H, hd, d_model), ("heads", "head_dim", "embed"),
                             scale=1.0 / math.sqrt(H * hd), dtype=dtype)
    if spec.qk_norm:  # Qwen3-style per-head RMSNorm on q and k
        p["q_norm"], a["q_norm"] = Param(None, (hd,), ("head_dim",), init="ones", dtype=dtype)
        p["k_norm"], a["k_norm"] = Param(None, (hd,), ("head_dim",), init="ones", dtype=dtype)
    return p, a


def _qk_normalize(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rot_dim(spec: AttnSpec) -> int:
    rd = int(spec.head_dim * spec.rope_frac)
    return rd - rd % 2


def _project_qkv(p, spec: AttnSpec, x, kv_x, q_positions, kv_positions,
                 heads_major: bool = False):
    """q (B,S,H,hd) and k/v (B,T,K,hd); heads before the sequence,
    (B,H,S,hd) and (B,K,T,hd), with ``heads_major``."""
    out, ann = ("bhsk", "group,{},,") if heads_major else ("bshk", "group,,{},")
    q = constrain_logical(jnp.einsum(f"bsd,dhk->{out}", x, p["wq"]),
                          ann.format("heads"))
    k = constrain_logical(jnp.einsum(f"bsd,dhk->{out}", kv_x, p["wk"]),
                          ann.format("kv_heads"))
    v = constrain_logical(jnp.einsum(f"bsd,dhk->{out}", kv_x, p["wv"]),
                          ann.format("kv_heads"))
    if spec.qk_norm:
        q = _qk_normalize(q, p["q_norm"])
        k = _qk_normalize(k, p["k_norm"])
    rd = _rot_dim(spec)
    if rd and not spec.cross:
        head_axis = -3 if heads_major else -2
        qc, qs = rope_frequencies(rd, q_positions, spec.rope_theta)
        kc, ks = rope_frequencies(rd, kv_positions, spec.rope_theta)
        q = apply_rope(q, qc, qs, rd, head_axis)
        k = apply_rope(k, kc, ks, rd, head_axis)
    return q, k, v


def _sdpa(q, k, v, mask, n_kv: int):
    """q (B,S,H,hd), k/v (B,T,K,hd), mask (B,S,T) or (S,T) bool or None.

    GQA via KV repetition to the full H heads: the score/probability tensors
    then shard over the heads axis (K alone rarely divides the model axis),
    at the cost of a 16x-sharded repeated-KV buffer. Decode, cross and
    non-causal attention take this path everywhere, and causal
    self-attention off the TPU (``attn_apply``)."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], n_kv
    G = H // K
    if G > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :], (B, T, K, G, hd)).reshape(B, T, H, hd)
        v = jnp.broadcast_to(v[:, :, :, None, :], (B, T, K, G, hd)).reshape(B, T, H, hd)
    k = constrain_logical(k, "group,,heads,")
    v = constrain_logical(v, "group,,heads,")
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32)
    scores = constrain_logical(scores / math.sqrt(hd), "group,heads,,")
    if mask is not None:
        m = mask if mask.ndim == 3 else mask[None]
        scores = jnp.where(m[:, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", w, v)


def causal_window_mask(S: int, T: int, window: Optional[int],
                       offset: int = 0) -> jnp.ndarray:
    """(S, T) bool; query i is at absolute position offset+i, key j at j."""
    qi = jnp.arange(S)[:, None] + offset
    kj = jnp.arange(T)[None, :]
    m = kj <= qi
    if window is not None:
        m &= (qi - kj) < window
    return m


@contextlib.contextmanager
def count_attn_paths():
    """Count, while tracing, the attention call sites by the path each
    takes: ``flash`` (the Pallas kernels where the program lowers for a
    TPU) or ``dense`` (``_sdpa``). A scanned stack of layers is one site."""
    counts = {"flash": 0, "dense": 0}
    _PATH_COUNTS.append(counts)
    try:
        yield counts
    finally:
        _PATH_COUNTS.pop()


def _flash_blocks(spec: AttnSpec, S: int, T: int):
    """The kernels' (bq, bk) where this call can take them, else None:
    causal self-attention the blocks tile, in a replica that no mesh axis
    shards (the heads would otherwise be split across chips)."""
    if not spec.causal or spec.cross or S != T:
        return None
    dist = current_distribution()
    if dist is not None and dist.shard_axes:
        return None
    return flash_blocks(S, T, spec.head_dim)


def _dense_attn(p, spec: AttnSpec, x, kv_x, positions, kv_positions):
    S, T = x.shape[1], kv_x.shape[1]
    q, k, v = _project_qkv(p, spec, x, kv_x, positions, kv_positions)
    mask = None
    if spec.causal and not spec.cross:
        mask = causal_window_mask(S, T, spec.window)
    with jax.named_scope("sdpa"):
        out = _sdpa(q, k, v, mask, spec.n_kv_heads)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def _flash_attn(p, spec: AttnSpec, x, positions, blocks, interpret=False):
    """Causal self-attention through the flash kernels: heads-major
    projections, q and k in v's dtype (the operands an f32 einsum's default
    precision feeds the MXU), one kernel call per replica."""
    q, k, v = _project_qkv(p, spec, x, x, positions, positions,
                           heads_major=True)
    attend = functools.partial(flash_attention, causal=True,
                               window=spec.window, block_q=blocks[0],
                               block_k=blocks[1], interpret=interpret)
    dist = current_distribution()
    if dist is not None:
        # a Mosaic call is not partitioned by XLA: run it per device. Inside
        # the step's replica vmap, whose spmd_axis_name shards the replica
        # axis over the dp axes, each device holds its replica whole.
        attend = jax.shard_map(attend, mesh=dist.mesh, in_specs=P(),
                               out_specs=P(), check_vma=False)
    with jax.named_scope("sdpa"):
        out = attend(q.astype(v.dtype), k.astype(v.dtype), v)
    return jnp.einsum("bhsk,hkd->bsd", out, p["wo"])


def attn_apply(p, spec: AttnSpec, x: jnp.ndarray,
               memory: Optional[jnp.ndarray] = None,
               positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full-sequence attention. ``memory`` => cross-attention (no mask).

    Causal self-attention that the flash kernels tile lowers to them on a
    TPU and to ``_sdpa`` elsewhere; everything else takes ``_sdpa``."""
    S = x.shape[1]
    kv_x = memory if spec.cross else x
    T = kv_x.shape[1]
    if positions is None:
        positions = jnp.arange(S)[None]
    kv_positions = jnp.arange(T)[None] if spec.cross else positions
    dense = functools.partial(_dense_attn, p, spec, x, kv_x, positions,
                              kv_positions)
    blocks = _flash_blocks(spec, S, T)
    if _PATH_COUNTS:
        _PATH_COUNTS[-1]["dense" if blocks is None else "flash"] += 1
    if blocks is None:
        return dense()
    return jax.lax.platform_dependent(
        tpu=functools.partial(_flash_attn, p, spec, x, positions, blocks),
        default=dense)


# ------------------------------------------------------------- decode
def attn_cache_init(spec: AttnSpec, batch: int, seq_len: int, dtype):
    L = cache_len(seq_len, spec.window)
    shp = (batch, L, spec.n_kv_heads, spec.head_dim)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


def attn_decode(p, spec: AttnSpec, x1: jnp.ndarray, cache: Dict,
                pos: jnp.ndarray,
                memory_kv: Optional[Tuple] = None) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode. x1 (B,1,d); pos scalar int32 (current position).
    ``memory_kv`` = (k_mem, v_mem) for cross-attention layers (static)."""
    B = x1.shape[0]
    if spec.cross:
        k, v = memory_kv
        q = jnp.einsum("bsd,dhk->bshk", x1, p["wq"])
        if spec.qk_norm:
            q = _qk_normalize(q, p["q_norm"])
        out = _sdpa(q, k, v, None, spec.n_kv_heads)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache
    q, k1, v1 = _project_qkv(p, spec, x1, x1,
                             jnp.full((1, 1), pos), jnp.full((1, 1), pos))
    L = cache["k"].shape[1]
    slot = pos % L if spec.window is not None else pos
    ck = jax.lax.dynamic_update_slice(cache["k"], k1.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v1.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    idx = jnp.arange(L)
    if spec.window is None:
        valid = idx <= pos
    else:
        # ring buffer: slot j holds absolute position j + L*floor stuff; valid
        # entries are those written within the last `window` steps.
        age = (slot - idx) % L
        valid = (age < jnp.minimum(pos + 1, L))
    mask = jnp.broadcast_to(valid[None, None, :], (B, 1, L))
    out = _sdpa(q, ck, cv, mask, spec.n_kv_heads)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": ck, "v": cv}


# ===================================================================== MLA
def mla_init(key, d_model: int, spec: MLASpec, dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    H = spec.n_heads
    qk = spec.qk_nope_dim + spec.qk_rope_dim
    p, a = {}, {}
    p["wq_a"], a["wq_a"] = dense_param(ks[0], d_model, (spec.q_lora_rank,), "embed", ("latent",), dtype=dtype)
    p["q_norm"], a["q_norm"] = Param(None, (spec.q_lora_rank,), ("latent",), init="ones", dtype=dtype)
    p["wq_b"], a["wq_b"] = dense_param(ks[1], spec.q_lora_rank, (H, qk), "latent", ("heads", "head_dim"), dtype=dtype)
    p["wkv_a"], a["wkv_a"] = dense_param(
        ks[2], d_model, (spec.kv_lora_rank + spec.qk_rope_dim,), "embed", ("latent",), dtype=dtype)
    p["kv_norm"], a["kv_norm"] = Param(None, (spec.kv_lora_rank,), ("latent",), init="ones", dtype=dtype)
    p["wk_b"], a["wk_b"] = dense_param(
        ks[3], spec.kv_lora_rank, (H, spec.qk_nope_dim), "latent", ("heads", "head_dim"), dtype=dtype)
    p["wv_b"], a["wv_b"] = dense_param(
        ks[4], spec.kv_lora_rank, (H, spec.v_head_dim), "latent", ("heads", "head_dim"), dtype=dtype)
    p["wo"], a["wo"] = Param(ks[5], (H, spec.v_head_dim, d_model),
                             ("heads", "head_dim", "embed"),
                             scale=1.0 / math.sqrt(H * spec.v_head_dim), dtype=dtype)
    return p, a


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _mla_q(p, spec: MLASpec, x, positions):
    q_lat = _rms(x @ p["wq_a"], p["q_norm"])
    q = jnp.einsum("bsl,lhk->bshk", q_lat, p["wq_b"])
    q_nope = q[..., :spec.qk_nope_dim]
    q_rope = q[..., spec.qk_nope_dim:]
    c, s = rope_frequencies(spec.qk_rope_dim, positions, spec.rope_theta)
    q_rope = apply_rope(q_rope, c, s)
    return q_nope, q_rope


def _mla_latent_kv(p, spec: MLASpec, x, positions):
    kv = x @ p["wkv_a"]
    c_kv = _rms(kv[..., :spec.kv_lora_rank], p["kv_norm"])
    k_rope = kv[..., spec.kv_lora_rank:]          # shared across heads
    c, s = rope_frequencies(spec.qk_rope_dim, positions, spec.rope_theta)
    k_rope = apply_rope(k_rope[..., None, :], c, s)[..., 0, :]
    return c_kv, k_rope


def mla_apply(p, spec: MLASpec, x: jnp.ndarray,
              positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None]
    q_nope, q_rope = _mla_q(p, spec, x, positions)
    q_nope = constrain_logical(q_nope, "group,,heads,")
    c_kv, k_rope = _mla_latent_kv(p, spec, x, positions)
    k_nope = constrain_logical(
        jnp.einsum("btl,lhk->bthk", c_kv, p["wk_b"]), "group,,heads,")
    v = constrain_logical(
        jnp.einsum("btl,lhk->bthk", c_kv, p["wv_b"]), "group,,heads,")
    scale = 1.0 / math.sqrt(spec.qk_nope_dim + spec.qk_rope_dim)
    with jax.named_scope("sdpa"):
        scores = (jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
                  + jnp.einsum("bshk,btk->bhst", q_rope, k_rope)
                  ).astype(jnp.float32) * scale
        scores = constrain_logical(scores, "group,heads,,")
        mask = causal_window_mask(S, S, spec.window)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, -1).astype(v.dtype)
        out = jnp.einsum("bhst,bthk->bshk", w, v)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def mla_cache_init(spec: MLASpec, batch: int, seq_len: int, dtype):
    L = cache_len(seq_len, spec.window)
    return {"c_kv": jnp.zeros((batch, L, spec.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, L, spec.qk_rope_dim), dtype)}


def mla_decode(p, spec: MLASpec, x1: jnp.ndarray, cache: Dict,
               pos: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """Absorbed-latent decode: attention runs in the kv_lora_rank space —
    per-token cache is (kv_lora + rope_dim) floats, MLA's headline saving."""
    B = x1.shape[0]
    pos2 = jnp.full((1, 1), pos)
    q_nope, q_rope = _mla_q(p, spec, x1, pos2)          # (B,1,H,*)
    c1, kr1 = _mla_latent_kv(p, spec, x1, pos2)          # (B,1,lat), (B,1,rope)
    L = cache["c_kv"].shape[1]
    slot = pos % L if spec.window is not None else pos
    c_kv = jax.lax.dynamic_update_slice(cache["c_kv"], c1.astype(cache["c_kv"].dtype), (0, slot, 0))
    k_rope = jax.lax.dynamic_update_slice(cache["k_rope"], kr1.astype(cache["k_rope"].dtype), (0, slot, 0))
    # absorb wk_b into the query: q_lat (B,1,H,lat)
    q_lat = jnp.einsum("bshk,lhk->bshl", q_nope, p["wk_b"])
    scale = 1.0 / math.sqrt(spec.qk_nope_dim + spec.qk_rope_dim)
    scores = (jnp.einsum("bshl,btl->bhst", q_lat, c_kv)
              + jnp.einsum("bshk,btk->bhst", q_rope, k_rope)).astype(jnp.float32) * scale
    idx = jnp.arange(L)
    if spec.window is None:
        valid = idx <= pos
    else:
        age = (slot - idx) % L
        valid = age < jnp.minimum(pos + 1, L)
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, -1).astype(c_kv.dtype)
    lat = jnp.einsum("bhst,btl->bshl", w, c_kv)          # (B,1,H,lat)
    out = jnp.einsum("bshl,lhk->bshk", lat, p["wv_b"])   # absorb wv_b on output
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"c_kv": c_kv, "k_rope": k_rope}
