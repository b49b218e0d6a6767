"""Quantized gossip wire formats: int8 / fp8-e4m3 bucket encode + decode.

GossipGraD's exchange is already O(1) bytes per step; this module shrinks the
constant. The ppermute payload of a gossip bucket is encoded on the dispatch
side — stochastic-rounded int8 (or deterministic fp8-style e4m3) codes plus
one fp32 scale per ``(row, 128)``-tile — and decoded inside the arrival-mix /
fused-update sweep (the scale is a per-row column stream, exactly the shape
the LARS trust-scale path already feeds the kernels). Params, moments and
gradients stay full precision; ONLY the wire payload shrinks.

Wire payload formats (``WireFormat.dtype``):

    fp32   the raw bucket, unencoded (the PR-1..5 wire — the default);
    bf16   plain downcast (2x), no scales;
    int8   stochastic-rounded symmetric int8, per-tile fp32 scale (4x codes);
    fp8    e4m3 emulated via ml_dtypes float8_e4m3fn, per-tile fp32 scale
           (4x codes; deterministic round-to-nearest — e4m3's mantissa
           makes stochastic rounding a wash, and the scale amax/448 keeps
           every scaled value <= 448, the format's max finite: e4m3fn has
           no inf, so an out-of-range cast would produce nan).

A quantized payload is a dict ``{"q": codes (..., rows, 128), "s": scales
fp32 (..., rows)}``: the codes keep the bucket's storage shape, and the
scales are that shape's leading dims, so the bucket's PartitionSpec minus
its lane entry applies to them (bucket strides are LANE multiples, hence
whole rows on every shard of a shard-local layout).

**Determinism discipline** (the ``exchange_ok`` splitmix32 discipline): the
stochastic-rounding noise is a pure integer hash keyed on (dispatch step,
replica rank, bucket index, seed) per element — no ``jax.random`` — so the
``core.simulate`` oracle, the shard_map engines, and resumed runs agree
bit-for-bit. Shard-local (fsdp) layouts pass ``base_index`` = the shard's
global element offset, so every element's noise is keyed by its GLOBAL
position in the bucket regardless of how the bucket is sharded.

This module depends only on jax/numpy (no repro.core import), so the core
engines can import it freely.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = [
    "WIRE_DTYPES",
    "WireFormat",
    "wire_key",
    "wire_uniform",
    "encode_wire",
    "decode_wire",
    "dequant_tiles",
    "zero_payload_like",
    "payload_spec",
    "wire_itemsize",
]

LANE = 128

WIRE_DTYPES = ("fp32", "bf16", "int8", "fp8")

_INT8_MAX = 127.0
_FP8_MAX = 448.0  # float8_e4m3fn max finite (no inf: overflow casts to nan)


@dataclasses.dataclass(frozen=True)
class WireFormat:
    """Wire-format knobs for the packed gossip engines.

    ``dtype`` picks the payload encoding (see module docstring); ``subset``
    is the partition-sampling fraction — the rotating bucket-subset schedule
    (core.topology.build_subset_schedule) sends ``ceil(subset*num_buckets)``
    buckets per exchange; ``seed`` keys the stochastic-rounding hash (and is
    independent of the drop-injection seed)."""

    dtype: str = "fp32"
    subset: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.dtype not in WIRE_DTYPES:
            raise ValueError(
                f"unknown wire dtype {self.dtype!r}; options {WIRE_DTYPES}")
        if not (0.0 < float(self.subset) <= 1.0):
            raise ValueError(
                f"gossip subset fraction must be in (0, 1], got {self.subset}")

    @property
    def is_default(self) -> bool:
        """True when this format is the uncompressed full-participation wire
        — the engines then take the PR-1..5 code path, bit-for-bit."""
        return self.dtype == "fp32" and float(self.subset) >= 1.0

    @property
    def quantized(self) -> bool:
        return self.dtype in ("int8", "fp8")


# ----------------------------------------------------------- splitmix32 hash
# Local copy of the exchange_ok finalizer (core.async_gossip._mix32): the
# wire noise must not couple to the drop-injection stream, so the two hashes
# share the finalizer but mix their keys with different constants.

def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix32 finalizer over uint32 (wrapping arithmetic)."""
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def wire_key(t, rank, bucket_index: int, seed: int = 0) -> jnp.ndarray:
    """Per-(dispatch step, replica rank, bucket) uint32 key of the
    stochastic-rounding stream. ``t`` and ``rank`` may be traced scalars or
    arrays (the simulator passes ``rank = arange(p)``); ``bucket_index`` and
    ``seed`` are static Python ints."""
    t = jnp.asarray(t).astype(jnp.uint32)
    r = jnp.asarray(rank).astype(jnp.uint32)
    x = (t * jnp.uint32(0x9E3779B9)
         ^ r * jnp.uint32(0x85EBCA6B)
         ^ jnp.uint32((int(bucket_index) * 0xC2B2AE35) & 0xFFFFFFFF)
         ^ jnp.uint32(int(seed) & 0xFFFFFFFF))
    return _mix32(x)


def wire_uniform(keys: jnp.ndarray, n: int, base_index=0) -> jnp.ndarray:
    """Uniform [0, 1) noise: one lane per element index, hashed from
    ``keys`` (shape = leading dims) x the GLOBAL element index
    ``base_index + arange(n)``. Returns shape ``keys.shape + (n,)`` fp32,
    quantized to 24 bits (the fp32-exact mantissa width). ``base_index``
    may be a Python int or a traced int32 scalar (shard-local engines
    derive it from ``axis_index`` inside shard_map)."""
    base = (jnp.uint32(int(base_index) & 0xFFFFFFFF)
            if isinstance(base_index, int)
            else jnp.asarray(base_index).astype(jnp.uint32))
    idx = ((base + jnp.arange(n, dtype=jnp.uint32))
           * jnp.uint32(0x9E3779B9))
    h = _mix32(jnp.asarray(keys, jnp.uint32)[..., None] ^ idx)
    return (h >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(
        1.0 / (1 << 24))


# ------------------------------------------------------------ encode/decode

def encode_wire(x: jnp.ndarray, wire_dtype: str, *, keys=None,
                base_index: int = 0, lane: int = LANE
                ) -> Union[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Encode one bucket, stored as ``(..., rows, lane)``, for the wire.

    fp32 returns ``x`` unchanged; bf16 a plain downcast. int8/fp8 return the
    ``{"q", "s"}`` payload dict: codes of ``x``'s shape and one fp32 scale
    ``amax / maxcode`` per ``(row, lane)`` tile, shaped ``(..., rows)``.
    int8 uses unbiased stochastic rounding ``floor(y + u)`` with ``u`` from
    ``wire_uniform(keys, n, base_index)`` (``keys`` from ``wire_key`` —
    required; ``n = rows * lane``, elements indexed row-major); fp8 rounds
    deterministically (cast RTNE), no keys needed. The exact fp32 op sequence here is the
    bit-exactness contract shared by the shard_map engines and the
    ``core.simulate`` oracle."""
    if wire_dtype == "fp32":
        return x
    if wire_dtype == "bf16":
        return x.astype(jnp.bfloat16)
    if wire_dtype not in ("int8", "fp8"):
        raise ValueError(
            f"unknown wire dtype {wire_dtype!r}; options {WIRE_DTYPES}")
    if x.ndim < 2 or x.shape[-1] != lane:
        raise ValueError(
            f"quantized wire needs a lane-multiple bucket stored as "
            f"(..., rows, {lane}), got {x.shape}")
    lead, rows = x.shape[:-2], int(x.shape[-2])
    n = rows * lane
    xf = x.astype(jnp.float32)
    maxcode = _INT8_MAX if wire_dtype == "int8" else _FP8_MAX
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = amax / jnp.float32(maxcode)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    y = xf * inv[..., None]
    if wire_dtype == "int8":
        if keys is None:
            raise ValueError("int8 wire needs the dispatch keys (wire_key) "
                             "for its stochastic rounding")
        u = wire_uniform(jnp.broadcast_to(jnp.asarray(keys, jnp.uint32),
                                          lead), n, base_index)
        q = jnp.clip(jnp.floor(y + u.reshape(lead + (rows, lane))),
                     -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
    else:
        # clamp before the cast: e4m3fn has no inf, and a scaled value that
        # rounds past the max finite (448) would encode as nan
        y = jnp.clip(y, -_FP8_MAX, _FP8_MAX)
        q = y.astype(jnp.float8_e4m3fn)
    return {"q": q, "s": scale}


def dequant_tiles(q: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """Decode codes ``(..., rows, lane)`` with per-tile scales ``(...,
    rows)`` to fp32: ``codes.astype(f32) * scale`` per tile — the SAME op
    the Pallas kernels run with the scale as a (rows, 1) column stream, so
    jnp decode and in-kernel decode are bit-identical."""
    return q.astype(jnp.float32) * s[..., None]


def decode_wire(payload) -> jnp.ndarray:
    """Payload -> mix operand: quantized dicts dequantize to fp32; raw
    fp32/bf16 payloads pass through (the mix casts to fp32 itself)."""
    if isinstance(payload, dict):
        return dequant_tiles(payload["q"], payload["s"])
    return payload


# --------------------------------------------------------- payload plumbing

def zero_payload_like(bucket: jnp.ndarray, wire_dtype: str):
    """The ring-slot filler for an unsent bucket (partition sampling) and
    the wire-ring bootstrap: an all-zero payload of the right wire shape.
    Zero codes x zero scales decode to exact zeros, and the slot is only
    ever consumed at alpha = 0."""
    if wire_dtype == "fp32":
        return jnp.zeros(bucket.shape, bucket.dtype)
    if wire_dtype == "bf16":
        return jnp.zeros(bucket.shape, jnp.bfloat16)
    qdt = jnp.int8 if wire_dtype == "int8" else jnp.float8_e4m3fn
    return {"q": jnp.zeros(bucket.shape, qdt),
            "s": jnp.zeros(bucket.shape[:-1], jnp.float32)}


def payload_spec(bucket_spec, wire_dtype: str):
    """PartitionSpec tree of one bucket's wire payload: the codes take the
    bucket's ``(dp, rows, lane)`` spec and the ``(dp, rows)`` scales the
    same spec without its (unsharded) lane entry."""
    if wire_dtype in ("int8", "fp8"):
        return {"q": bucket_spec, "s": P(*tuple(bucket_spec)[:-1])}
    return bucket_spec


def wire_itemsize(wire_dtype: str, bucket_dtype) -> int:
    """Bytes per CODE element on the wire (scales accounted separately —
    they ride the coefficient block, like the per-bucket scalars the fused
    kernels already ship)."""
    if wire_dtype == "fp32":
        return int(np.dtype(bucket_dtype).itemsize)
    return {"bf16": 2, "int8": 1, "fp8": 1}[wire_dtype]
