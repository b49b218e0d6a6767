"""Straggler / fault-tolerance benchmark for the bounded-delay gossip
runtime: step time and replica drift vs staleness k and injected drop rate.

Two sub-experiments, one JSON (``BENCH_straggler.json``):

**Step time (emulated wire, subprocess with forced host devices).** Runs the
REAL packed staleness-k ring engine (core.async_gossip) with a host-emulated
interconnect in which a fraction of exchanges *straggle* (their wire time is
several times the base latency). The payload dispatched at step t is due at
step t+k, so a deeper ring gives every exchange more compute to hide behind.
Two consumption policies are timed:

* ``wait``  — the runtime insists on every exchange: if the payload has not
  landed by its deadline the host stalls until it does (what a synchronous
  or must-deliver runtime pays a straggling peer);
* ``skip``  — GossipGraD's §4.2 premise: a late exchange is simply skipped
  (the ring consumes the slot with valid=0, alpha=0) and the step proceeds —
  step time stays flat, the cost is a (measured) fraction of skipped mixes.

**Replica drift (simulator, laptop scale).** The p-replica bounded-delay
sim (core.simulate.make_async_sim_train_step) trained on the bigram task
for a grid of (staleness, drop rate): final loss and replica variance — the
accuracy side of the fault-tolerance claim (drift grows gently with k and
drop rate; the GoSGD/Jin et al. bounded-staleness picture).

Wired into ``benchmarks/run.py --only straggler``; ``--smoke`` shrinks the
iteration counts for CI.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(ROOT, "BENCH_straggler.json")

_WIRE_SCRIPT = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.core import (PackedParams, build_layout, build_schedule,
                        init_inbox_ring, make_packed_async_gossip_mix,
                        packed_param_specs)

SMOKE = bool(int(sys.argv[1]))
WIRE_S = 0.02 if SMOKE else 0.04       # base emulated wire latency/exchange
STRAGGLE_P = 0.3                       # fraction of exchanges that straggle
STRAGGLE_X = 4.0                       # straggler wire-time multiplier
COMPUTE_ITERS = 30 if SMOKE else 60    # fwd/bwd+update stand-in depth
STEPS = 10 if SMOKE else 24
KS = (1, 2, 4)

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

def wire_time(t):
    # deterministic straggler draw per dispatch step
    u = (np.uint32(t) * np.uint32(2654435761) % np.uint32(1 << 16)) / float(1 << 16)
    return WIRE_S * (STRAGGLE_X if u < STRAGGLE_P else 1.0)

def make_engine(k):
    mix = make_packed_async_gossip_mix(mesh, ("data",), sched, layout,
                                       staleness=k)
    jmix = [jax.jit(lambda q, r, _ph=ph: mix(q, r, _ph))
            for ph in range(sched.period)]
    # warm up every phase variant + the compute program (policy only
    # changes the host loop, so both policies share these compilations)
    q = sh(params0)
    ring = init_inbox_ring(q, k, p)
    for ph in range(sched.period):
        _, ring = jmix[ph](q, ring)
    block((ring, compute(q)))
    return jmix

def run(k, policy, jmix):
    q = sh(params0)
    ring = init_inbox_ring(q, k, p)
    due = {}           # dispatch step -> wall time its payload lands
    stalls = skips = 0
    t0 = time.perf_counter()
    for t in range(STEPS):
        # consumption deadline for the payload dispatched k steps ago
        lands = due.pop(t - k, None)
        if lands is not None:
            late = lands - time.perf_counter()
            if late > 0:
                if policy == "wait":
                    time.sleep(late); stalls += 1
                else:
                    # skip-on-timeout: invalidate the slot about to be
                    # consumed, so the masked arrival mix really runs with
                    # alpha = 0 (the receive-timeout path, host-driven)
                    ring = dict(ring,
                                valid=ring["valid"].at[:, 0].set(0.0))
                    skips += 1
        mixed, ring = jmix[t % sched.period](q, ring)
        block(ring)    # exchange data produced -> payload enters the wire
        due[t] = time.perf_counter() + wire_time(t)
        q = compute(mixed)
        block(q)       # pace the loop at device compute speed: the payload
                       # has k REAL compute steps to cross the emulated wire
    wall = (time.perf_counter() - t0) / STEPS * 1e3
    return {"staleness": k, "policy": policy, "ms_per_step": wall,
            "stalls": stalls, "skipped_frac": skips / STEPS}

rows = []
for k in KS:
    jmix = make_engine(k)
    rows += [run(k, policy, jmix) for policy in ("wait", "skip")]
print(json.dumps({
    "p": p, "steps": STEPS, "wire_ms": WIRE_S * 1e3,
    "straggle_p": STRAGGLE_P, "straggle_x": STRAGGLE_X,
    "compute_iters": COMPUTE_ITERS,
    "n_buckets": layout.num_buckets,
    "bucket_sizes": list(layout.bucket_sizes),
    "rows": rows,
}))
"""


def _drift_rows(smoke: bool):
    """Replica drift / final loss vs (staleness, drop rate) on the sim."""
    import numpy as np

    from .common import run_replica_lm

    steps = 40 if smoke else 100
    out = []
    for k in (1, 2, 4):
        for drop_pct in (0, 30):
            proto = f"gossip_async_k{k}" + (f"_drop{drop_pct}" if drop_pct
                                            else "")
            hist, _ = run_replica_lm(8, proto, steps, seq_len=32,
                                     batch_per_replica=4, lr=0.3, seed=1)
            out.append({
                "staleness": k,
                "drop_rate": drop_pct / 100.0,
                "final_loss": float(np.mean([h["loss"] for h in hist[-10:]])),
                "replica_variance": hist[-1]["replica_variance"],
            })
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
            f"straggler bench subprocess failed:\n{r.stdout}\n{r.stderr}")
    wire = json.loads(r.stdout.strip().splitlines()[-1])
    drift = _drift_rows(smoke)
    record = {"smoke": smoke, "wire": wire, "drift": drift}
    with open(BENCH_JSON, "w") as f:
        json.dump(record, f, indent=1)
    out = []
    for row in wire["rows"]:
        out.append((
            f"straggler_k{row['staleness']}_{row['policy']}",
            row["ms_per_step"] * 1e3,
            f"stalls={row['stalls']};skipped={row['skipped_frac']:.2f}"))
    for row in drift:
        out.append((
            f"drift_k{row['staleness']}_drop{int(row['drop_rate']*100)}",
            row["final_loss"] * 1e6,
            f"loss={row['final_loss']:.4f};"
            f"replica_var={row['replica_variance']:.2e}"))
    return out
