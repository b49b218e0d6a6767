#!/usr/bin/env python3
"""Chip smoke test: train qwen3-0.6b at its published widths on TPU v5e
through the launcher's own functions (repro.launch.train -> Trainer).

    python chip_smoke.py              # one chip: gossip --packed, 28 layers
    python chip_smoke.py --chips 4    # four one-chip replicas: gossip vs agd

One chip: the fused mix+SGD Pallas kernel must appear in the compiled step
as a Mosaic ``tpu_custom_call``, match its jnp twin on a small bucket, and
the losses must be finite with the first one near ln(vocab). Four chips:
``--protocol gossip --packed`` against ``--protocol agd --packed`` as
data=4 replicas; gossip's step must hold a collective-permute, agd's an
all-reduce, and each replica's parameters must live on its own chip.

Earlier lines report the device, compile seconds, the median step time
(host clock around steps that end in ``block_until_ready``) and the peak
device memory. The last line is ``{"ok": true, "device": {...}}``. Without a
TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SEQ_LEN = 1024
LOCAL_BATCH = 4     # sequences per chip: 4 x 1024 tokens fit 16 GB with remat
WARM_STEPS = 5
_T0 = time.perf_counter()


def _elapsed() -> str:
    return f"{time.perf_counter() - _T0:.1f} s"


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _launcher_argv(protocol: str, chips: int, steps: int) -> list:
    return ["--arch", "qwen3-0.6b", "--protocol", protocol, "--packed",
            "--seq-len", str(SEQ_LEN),
            "--global-batch", str(LOCAL_BATCH * chips),
            "--num-rotations", "1", "--steps", str(steps), "--log-every", "1"]


def _train(protocol: str, chips: int, hlo_needs: str) -> dict:
    """Build the launcher's trainer, compile step phase 0 ahead of time (its
    HLO must contain ``hlo_needs``), run every schedule phase once, then
    time WARM_STEPS steps."""
    import jax
    from repro.launch import train

    args = train.build_parser().parse_args(
        _launcher_argv(protocol, chips, WARM_STEPS + 2))
    t0 = time.perf_counter()
    trainer, _ = train.build_trainer(args)
    setup_s = time.perf_counter() - t0
    cfg = trainer.bundle.cfg
    print(f"[{protocol}] {cfg.name}: d_model {cfg.d_model}, "
          f"{len(cfg.blocks)} layers, vocab {cfg.vocab}, "
          f"{cfg.param_dtype} params / {cfg.compute_dtype} compute, "
          f"data={trainer.bundle.dist.dp}, "
          f"{trainer.bundle.layout.num_buckets} buckets, "
          f"{SEQ_LEN}x{LOCAL_BATCH} tokens per chip; set-up {setup_s:.1f} s "
          f"(at {_elapsed()})")

    t0 = time.perf_counter()
    compiled = trainer.step_fn(0).lower(trainer.state,
                                        trainer.batch(0)).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    print(f"[{protocol}] compile {compile_s:.1f} s; step program: "
          f"{hlo.count('tpu_custom_call')} tpu_custom_call, "
          f"{hlo.count('collective-permute')} collective-permute and "
          f"{hlo.count('all-reduce')} all-reduce mentions; args "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temp "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
    if "tpu_custom_call" not in hlo:
        _fail(f"{protocol}: no tpu_custom_call in the step: the fused "
              "update did not compile as a Mosaic kernel")
    if hlo_needs not in hlo:
        _fail(f"{protocol}: no {hlo_needs} in the compiled step")

    params = jax.tree.leaves(trainer.state["params"])
    for leaf in params:
        devs = sorted(s.device.id for s in leaf.addressable_shards)
        if devs != sorted(d.id for d in jax.devices()) or any(
                s.data.shape[0] != 1 for s in leaf.addressable_shards):
            _fail(f"{protocol}: a parameter bucket is not one replica per "
                  f"device (shards on {devs})")

    period = max(trainer.bundle.protocol.period, 1)
    t0 = time.perf_counter()
    trainer.run(period)             # the first call of each phase compiles
    jax.block_until_ready(trainer.state)
    first_s = time.perf_counter() - t0
    times = []
    for step in range(period, period + WARM_STEPS):
        t0 = time.perf_counter()
        trainer.run(1, start_step=step)
        jax.block_until_ready(trainer.state)
        times.append(time.perf_counter() - t0)
    losses = [h["loss"] for h in trainer.history]
    if not all(math.isfinite(x) for x in losses):
        _fail(f"{protocol}: non-finite loss in {losses}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    med = statistics.median(times)
    tokens = SEQ_LEN * LOCAL_BATCH * chips
    print(f"[{protocol}] first {period} step(s) {first_s:.2f} s; warm step "
          f"median {med * 1e3:.1f} ms (all {[round(t * 1e3, 1) for t in times]}"
          f" ms), {tokens / med:.0f} tokens/s; peak_bytes_in_use "
          f"{peak / 1e9:.3f} GB; losses {[round(x, 4) for x in losses]}")
    return {"losses": losses, "vocab": cfg.vocab}


def _check_kernel_twin() -> None:
    """The fused SGD kernel on the chip against its jnp twin (the repo's own
    oracle, kernels/fused_update.py) on one small bucket."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ops import fused_sgd_bucket

    keys = jax.random.split(jax.random.key(1), 4)
    p, g, b, m = (jax.random.normal(k, (1, 2048, 128), jnp.float32)
                  for k in keys)
    outs = {impl: jax.jit(lambda *a, impl=impl: fused_sgd_bucket(
        *a, lr=0.1, alpha=0.5, momentum=0.9, impl=impl))(p, g, b, m)
        for impl in ("pallas", "jnp")}
    for got, want in zip(outs["pallas"], outs["jnp"]):
        err = float(jnp.max(jnp.abs(got - want)))
        print(f"[kernel] fused_sgd pallas vs jnp twin: max |diff| {err:.3g}")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    chips = ap.parse_args().chips

    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no repro package under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.launch.cache import setup_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)")
    if len(devs) != chips:
        _fail(f"--chips {chips} needs {chips} TPU device(s), found {len(devs)}")
    print(f"device: {devs[0].device_kind} x {len(devs)} (backend up at "
          f"{_elapsed()}); compile cache {setup_compile_cache()}")

    if chips == 1:
        _check_kernel_twin()
        out = _train("gossip", 1, "tpu_custom_call")
        first, ln_v = out["losses"][0], math.log(out["vocab"])
        if abs(first - ln_v) > 1.0:
            _fail(f"first loss {first:.4f} is far from ln(vocab) {ln_v:.4f}")
    else:
        gossip = _train("gossip", 4, "collective-permute")
        gc.collect()    # free gossip's state before agd's is placed
        agd = _train("agd", 4, "all-reduce")
        # same init and data: the step-0 losses are computed before any
        # update and must agree across protocols
        g0, a0 = gossip["losses"][0], agd["losses"][0]
        if abs(g0 - a0) > 1e-2:
            _fail(f"step-0 loss differs: gossip {g0:.5f} vs agd {a0:.5f}")

    from repro.launch.train import device_info
    print(f"all checks passed at {_elapsed()}")
    print(json.dumps({"ok": True, "device": device_info()}))


if __name__ == "__main__":
    main()
