"""Pallas TPU kernel: chunked Mamba selective-scan  h_t = dA_t*h_{t-1} + dBx_t.

The recurrence is sequential in time but elementwise in (channel, state), so
the TPU-native layout is: tile channels into VMEM-sized blocks, stream the
sequence through in chunks, and carry the running state h in a VMEM scratch
accumulator across chunk grid-steps (TPU grids execute sequentially on a
core, which is exactly what a scan needs — no GPU-style inter-block
synchronization to emulate).

Grid: (batch, channel_blocks, seq_chunks) — seq innermost so the carried
scratch state is valid; it is (re)initialized whenever a new (b, d) tile
starts (chunk index 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_scan_chunked"]


def _scan_kernel(dA_ref, dBx_ref, h_ref, carry_ref, *, chunk: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    h = carry_ref[...]                     # (bd, N) f32

    def step(t, h):
        h = dA_ref[0, t] * h + dBx_ref[0, t]
        h_ref[0, t] = h
        return h

    h = jax.lax.fori_loop(0, chunk, step, h)
    carry_ref[...] = h


def ssm_scan_chunked(dA: jnp.ndarray, dBx: jnp.ndarray, *,
                     chunk: int = 128, block_d: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """dA, dBx: (B, S, D, N) float32 -> h (B, S, D, N).

    ``chunk`` divides S; ``block_d`` tiles the channel dim. VMEM per step:
    2 * chunk*block_d*N*4B inputs + chunk*block_d*N*4B output + carry."""
    B, S, D, N = dA.shape
    assert dA.shape == dBx.shape
    bd = min(block_d, D)
    ch = min(chunk, S)
    assert S % ch == 0 and D % bd == 0, (S, ch, D, bd)
    grid = (B, D // bd, S // ch)
    io_spec = pl.BlockSpec((1, ch, bd, N), lambda b, d, c: (b, c, d, 0))
    return pl.pallas_call(
        functools.partial(_scan_kernel, chunk=ch),
        grid=grid,
        in_specs=[io_spec, io_spec],
        out_specs=io_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, D, N), dA.dtype),
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=interpret, name="ssm_scan",
    )(dA, dBx)
