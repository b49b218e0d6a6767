"""Sync vs async gossip step time under a comm-inflated config.

Runs the REAL packed gossip engines (core.gossip.make_packed_gossip_mix vs
core.async_gossip.make_packed_async_gossip_mix) on forced host devices in a
subprocess, with a fwd/bwd+update stand-in between exchanges and an
**emulated interconnect latency**: forced host devices share one memory
space, so a ppermute is a memcpy with no real wire — the latency a TPU pays
on ICI is modeled as a host-side wait attached to the exchange.

The structural difference this measures is exactly the paper's §5 claim:

* sync gossip: the step's exchange must LAND before the next step can start
  — wall/step = compute + mix + wire.
* gossip_async: the exchange dispatched at step t is only consumed as step
  t+1's inbox, so its wire time runs concurrently with step t's compute —
  wall/step = mix + max(compute, wire).

On a real TPU mesh the same overlap happens inside the compiled step (XLA
hoists the fwd/bwd between collective-permute-start/done); here the async
mix is its own dispatch so the host-emulated wire can overlap the compute
program. The mesh is p=2 (this container has 2 cores — more forced devices
just thrash the scheduler); the protocol machinery is identical at any p.
Results land in ``BENCH_async_gossip.json`` (repo root) next to
``BENCH_gossip_mix.json``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(ROOT, "BENCH_async_gossip.json")

_SCRIPT = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.core import (PackedParams, build_layout, build_schedule,
                        init_inbox_ring, make_packed_gossip_mix,
                        make_packed_async_gossip_mix, packed_param_specs)

SMOKE = bool(int(sys.argv[1]))
WIRE_S = 0.04 if SMOKE else 0.08       # emulated interconnect latency/step
COMPUTE_ITERS = 50 if SMOKE else 100   # fwd/bwd+update stand-in depth
STEPS = 8 if SMOKE else 20

p = 2
from repro.launch.mesh import make_mesh
mesh = make_mesh((p,), ("data",))
sched = build_schedule(p, num_rotations=2, seed=0)
rng = np.random.default_rng(0)
# ~1 MiB per replica across odd-sized leaves -> a few buckets
TARGET_BUCKET_BYTES = 1 << 18
tree = {f"w{i}": jnp.asarray(rng.normal(size=(p, n)), jnp.float32)
        for i, n in enumerate((1 << 16, 3 * (1 << 15), 1 << 15, 130))}
layout = build_layout(tree, skip_leading=1,
                      target_bucket_bytes=TARGET_BUCKET_BYTES)
params0 = PackedParams.pack(tree, layout)
specs = packed_param_specs(layout, ("data",))
sh = lambda t: jax.tree.map(
    lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), t, specs,
    is_leaf=lambda x: not isinstance(x, (PackedParams, tuple)))

sync_mix = make_packed_gossip_mix(mesh, ("data",), sched, layout)
async_mix = make_packed_async_gossip_mix(mesh, ("data",), sched, layout)
# jit per static phase: in the trainer the mix runs inside the jitted train
# step; bare shard_map calls would re-trace per call and swamp the timing
jit_sync = [jax.jit(lambda t, _ph=ph: sync_mix(t, _ph))
            for ph in range(sched.period)]
jit_async = [jax.jit(lambda t, b, _ph=ph: async_mix(t, b, _ph))
             for ph in range(sched.period)]

@jax.jit
def compute(q):  # fwd/bwd + optimizer update stand-in over the buckets
    def body(x):
        return jax.lax.fori_loop(
            0, COMPUTE_ITERS,
            lambda i, v: v * 0.99995 + jnp.tanh(v) * 1e-4, x)
    return jax.tree.map(body, q)

def block(t):
    jax.block_until_ready(jax.tree.leaves(t))

def warmup():
    # compile every phase variant + compute so timed loops measure steps
    q = sh(params0); ring = init_inbox_ring(q, 1, p)
    for ph in range(sched.period):
        q = jit_sync[ph](q)
        _, ring = jit_async[ph](q, ring)
    block((q, ring, compute(q)))

def run_sync():
    q = sh(params0)
    t0 = time.perf_counter()
    for t in range(STEPS):
        u = compute(q)
        q = jit_sync[t % sched.period](u)
        block(q)             # the exchange must land...
        time.sleep(WIRE_S)   # ...and its wire latency is on the critical path
    return (time.perf_counter() - t0) / STEPS * 1e3

def run_async():
    q = sh(params0)
    ring = init_inbox_ring(q, 1, p)   # staleness-1: the PR-2 configuration
    t0 = time.perf_counter()
    for t in range(STEPS):
        mixed, outring = jit_async[t % sched.period](q, ring)
        q = compute(mixed)     # dispatched; runs while the wire settles
        block(outring)         # exchange data produced (mix program done)
        time.sleep(WIRE_S)     # wire latency overlaps compute(q) above
        ring = outring         # payload lands as the ring's newest slot
    block(q)
    return (time.perf_counter() - t0) / STEPS * 1e3

warmup()
sync_ms = run_sync()
async_ms = run_async()
print(json.dumps({
    "p": p, "steps": STEPS, "wire_ms": WIRE_S * 1e3,
    "compute_iters": COMPUTE_ITERS,
    "bytes_per_replica": layout.padded_bytes(),
    # the layout actually used: this bench forces small buckets to exercise
    # multi-bucket pipelining, so its bucket count differs from
    # kernels_bench's default-size layout by design — emit both so
    # BENCH_*.json stay comparable across PRs
    "n_buckets": layout.num_buckets,
    "target_bucket_bytes": TARGET_BUCKET_BYTES,
    "bucket_sizes": list(layout.bucket_sizes),
    "bucket_dtypes": list(layout.bucket_dtypes),
    "sync_gossip_ms_per_step": sync_ms,
    "gossip_async_ms_per_step": async_ms,
    "async_speedup": sync_ms / max(async_ms, 1e-9),
}))
"""


def rows(smoke: bool = False):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", _SCRIPT, str(int(smoke))],
                       env=env, capture_output=True, text=True, timeout=600,
                       cwd=ROOT)
    if r.returncode != 0:
        raise RuntimeError(f"async bench subprocess failed:\n{r.stdout}\n{r.stderr}")
    record = json.loads(r.stdout.strip().splitlines()[-1])
    record["smoke"] = smoke
    with open(BENCH_JSON, "w") as f:
        json.dump(record, f, indent=1)
    return [
        ("gossip_sync_comm_inflated",
         record["sync_gossip_ms_per_step"] * 1e3,
         f"p={record['p']};wire_ms={record['wire_ms']:.0f}"),
        ("gossip_async_comm_inflated",
         record["gossip_async_ms_per_step"] * 1e3,
         f"speedup={record['async_speedup']:.2f}x;"
         f"buckets={record['n_buckets']}"),
    ]
