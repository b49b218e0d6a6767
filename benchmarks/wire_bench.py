"""Compressed + partition-sampled gossip wire benchmark: bytes/step,
step time under an emulated interconnect, and convergence drift vs
(wire dtype, bucket-subset fraction).  One JSON (``BENCH_wire.json``).

**Bytes + step time (emulated wire, subprocess with forced host devices).**
Runs the REAL packed sync gossip engine (core.gossip) with each wire format
over the same bucket layout; the exact per-chip payload of one exchange
comes from ``core.gossip.wire_bytes_per_step`` and the host sleeps
``total_bytes / EMU_BW`` per step, putting the wire on the critical path the
way a bandwidth-bound interconnect would.  The compressed wires do MORE
arithmetic per step (stochastic-rounding encode + in-sweep decode) and ship
FEWER bytes, so the measured ms/step shows the net effect: int8 cuts the
payload 4x (stochastic-rounded codes + per-128-tile fp32 scales), int8 +
50% partition sampling 8x, bf16 2x.

**Convergence drift (simulator, laptop scale).**  The p-replica bounded-delay
sim trained on the bigram task for one uncompressed reference and the wire
variants (``gossip_async_k2_q8``-style names, benchmarks.common.
parse_async_protocol): final loss and replica variance, plus their ratios
vs the fp32 wire — the accuracy side of the compression claim (the
acceptance band is within 2x of uncompressed, pinned by tests/test_wire.py).

Wired into ``benchmarks/run.py --only wire``; ``--smoke`` shrinks the
iteration counts for CI.  Only the ``ms_per_step`` leaves are gated by
benchmarks.check_regression — byte counts and losses are structural.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(ROOT, "BENCH_wire.json")

_WIRE_SCRIPT = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.core import (PackedParams, build_layout, build_schedule,
                        make_packed_gossip_mix, packed_param_specs,
                        wire_bytes_per_step, wire_period, wire_subset_of)
from repro.kernels.quantize import WireFormat

SMOKE = bool(int(sys.argv[1]))
EMU_BW = 20e6                          # bytes/s of the emulated interconnect
                                       # (slow enough that the exchange is
                                       # bandwidth-bound over the encode cost)
COMPUTE_ITERS = 30 if SMOKE else 60    # fwd/bwd+update stand-in depth
STEPS = 10 if SMOKE else 24
WIRES = [("fp32", 1.0), ("bf16", 1.0), ("int8", 1.0), ("fp8", 1.0),
         ("int8", 0.5)]

p = 2
from repro.launch.mesh import make_mesh
mesh = make_mesh((p,), ("data",))
sched = build_schedule(p, num_rotations=2, seed=0)
rng = np.random.default_rng(0)
tree = {f"w{i}": jnp.asarray(rng.normal(size=(p, n)), jnp.float32)
        for i, n in enumerate((1 << 16, 3 * (1 << 15), 1 << 15, 130))}
layout = build_layout(tree, skip_leading=1, target_bucket_bytes=1 << 18)
params0 = PackedParams.pack(tree, layout)
specs = packed_param_specs(layout, ("data",))
sh = lambda t: jax.tree.map(
    lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), t, specs,
    is_leaf=lambda x: not isinstance(x, (PackedParams, tuple)))

@jax.jit
def compute(q):  # fwd/bwd + optimizer update stand-in over the buckets
    def body(x):
        return jax.lax.fori_loop(
            0, COMPUTE_ITERS,
            lambda i, v: v * 0.99995 + jnp.tanh(v) * 1e-4, x)
    return jax.tree.map(body, q)

def block(t):
    jax.block_until_ready(jax.tree.leaves(t))

def run(wd, frac):
    wire = WireFormat(dtype=wd, subset=frac, seed=0)
    mix = make_packed_gossip_mix(mesh, ("data",), sched, layout, wire=wire)
    eff = wire_period(sched, wire_subset_of(wire, layout.num_buckets))
    jmix = [jax.jit(lambda q, _ph=ph: mix(q, _ph)) for ph in range(eff)]
    acct = wire_bytes_per_step(layout, wire)
    wire_s = acct["total_bytes"] / EMU_BW
    q = sh(params0)
    for ph in range(eff):              # warm up every phase + compute
        q = jmix[ph](q)
    block((q, compute(q)))
    q = sh(params0)
    t0 = time.perf_counter()
    for t in range(STEPS):
        q = jmix[t % eff](q)
        block(q)                       # exchange produced -> enters the wire
        time.sleep(wire_s)             # bandwidth-bound emulated transfer
        q = compute(q)
        block(q)
    wall = (time.perf_counter() - t0) / STEPS * 1e3
    return {"wire_dtype": wd, "subset": frac, "ms_per_step": wall,
            "bytes_per_step": acct["total_bytes"],
            "raw_bytes": acct["raw_bytes"],
            "reduction_codes": acct["reduction_codes"],
            "reduction_total": acct["reduction_total"]}

rows = [run(wd, frac) for wd, frac in WIRES]
print(json.dumps({
    "p": p, "steps": STEPS, "emu_bw_bytes_s": EMU_BW,
    "compute_iters": COMPUTE_ITERS,
    "n_buckets": layout.num_buckets,
    "bucket_sizes": list(layout.bucket_sizes),
    "rows": rows,
}))
"""

# one uncompressed reference + the wire variants (see parse_async_protocol),
# on the production-shaped staleness-4 ring
_DRIFT_PROTOCOLS = ("gossip_async_k4", "gossip_async_k4_q8",
                    "gossip_async_k4_qf8", "gossip_async_k4_sub50",
                    "gossip_async_k4_q8_sub50")


def _tag(proto: str) -> str:
    return proto.replace("gossip_async_k4", "k4").lstrip("_") or "k4"


def _drift_rows(smoke: bool):
    """Final loss / replica drift per wire variant on the sim, with ratios
    against the uncompressed fp32 reference (same seeds and batches).

    Both loss and variance are tail means over the last 10 steps (a single
    last-step variance sample swings ~10% run to run).  Expected shape:
    quantized wires add noise-floor drift (int8 ~1.1x, fp8 ~1.3-1.6x) at
    unchanged loss; 50%-sampled wires sit at the diffusion-rate bound —
    half the exchanges per step means ~2x the stationary replica variance
    (the PR-4 row-stochastic skip algebra, applied every other bucket) —
    again at unchanged-or-better loss.  The hard acceptance band (drift
    and loss within 2x of uncompressed on the quadratic sim) is pinned by
    tests/test_wire.py, not here."""
    import numpy as np

    from .common import run_replica_lm

    steps = 40 if smoke else 100
    out = []
    for proto in _DRIFT_PROTOCOLS:
        hist, _ = run_replica_lm(8, proto, steps, seq_len=32,
                                 batch_per_replica=4, lr=0.3, seed=1)
        out.append({
            "protocol": proto,
            "final_loss": float(np.mean([h["loss"] for h in hist[-10:]])),
            "replica_variance": float(np.mean(
                [h["replica_variance"] for h in hist[-10:]])),
        })
    ref = out[0]
    for row in out:
        row["loss_vs_fp32"] = row["final_loss"] / max(ref["final_loss"], 1e-9)
        row["drift_vs_fp32"] = (row["replica_variance"]
                                / max(ref["replica_variance"], 1e-12))
    return out


def rows(smoke: bool = False):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", _WIRE_SCRIPT, str(int(smoke))],
                       env=env, capture_output=True, text=True, timeout=600,
                       cwd=ROOT)
    if r.returncode != 0:
        raise RuntimeError(
            f"wire bench subprocess failed:\n{r.stdout}\n{r.stderr}")
    wire = json.loads(r.stdout.strip().splitlines()[-1])
    drift = _drift_rows(smoke)
    record = {"smoke": smoke, "wire": wire, "drift": drift}
    with open(BENCH_JSON, "w") as f:
        json.dump(record, f, indent=1)
    out = []
    for row in wire["rows"]:
        sub = f"_sub{int(row['subset'] * 100)}" if row["subset"] < 1.0 else ""
        out.append((
            f"wire_{row['wire_dtype']}{sub}",
            row["ms_per_step"] * 1e3,
            f"bytes={int(row['bytes_per_step'])};"
            f"codes={row['reduction_codes']:.2f}x;"
            f"total={row['reduction_total']:.2f}x"))
    for row in drift:
        out.append((
            f"wire_drift_{_tag(row['protocol'])}",
            row["final_loss"] * 1e6,
            f"loss_vs_fp32={row['loss_vs_fp32']:.3f};"
            f"drift_vs_fp32={row['drift_vs_fp32']:.3f}"))
    return out
