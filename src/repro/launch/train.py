"""Training launcher CLI.

Trains the named config at its published widths on the devices present:
under ``dist_mode="replica"`` every device holds one model replica (one
chip: data=1; a four-chip host: data=4). ``--smoke`` is the only thing that
shrinks a config (to ``--d-model`` in fp32, remat off, on the ``--smoke-mesh``
of possibly forced-host CPU devices). The gossip phase cycles through the
schedule with one compiled step per phase (static mode).

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen3-0.6b --protocol gossip --steps 50 --smoke

The last line printed is a JSON summary naming the device it ran on.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Dict, List, Optional

import jax

from repro.checkpoint import (checkpoint_exists, read_manifest, restore_state,
                              save_state)
from repro.configs import get_config, list_archs
from repro.data import ShardedTokenDataset
from repro.launch.cache import setup_compile_cache
from repro.launch.mesh import make_device_mesh, make_smoke_mesh
from repro.launch.specs import train_input_specs
from repro.models import reduced
from repro.optim import scale_lr_sqrt_p, sgd, step_decay
from repro.train import (Trainer, init_train_state, make_distribution,
                         make_train_step_bundle)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", default="qwen3-0.6b", choices=list_archs())
    ap.add_argument("--protocol", default="gossip",
                    choices=["gossip", "gossip_async", "agd", "every_logp",
                             "none"])
    ap.add_argument("--topology", default="dissemination",
                    choices=["dissemination", "hypercube"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--num-rotations", type=int, default=2)
    ap.add_argument("--staleness", type=int, default=1,
                    help="gossip_async inbox-ring depth k (bounded delay): "
                    "the exchange dispatched at step t is consumed at step "
                    "t+k, so the wire has k full steps of compute to land")
    ap.add_argument("--drop-timeout", type=float, default=0.0,
                    metavar="RATE",
                    help="emulated-wire fault injection: probability that "
                    "an exchange misses its staleness-k deadline and is "
                    "skipped (mixed with alpha=0); deterministic per "
                    "(step, rank) so resumed runs replay the same drops")
    ap.add_argument("--drop-seed", type=int, default=0)
    ap.add_argument("--wire-dtype", default="fp32",
                    choices=["fp32", "bf16", "int8", "fp8"],
                    help="gossip wire payload encoding (needs --packed for "
                    "non-fp32): int8 = stochastic-rounded codes + per-128-"
                    "tile fp32 scales (4x fewer bytes), fp8 = e4m3 ditto, "
                    "bf16 = plain downcast; decode happens inside the "
                    "arrival-mix / fused-update sweep")
    ap.add_argument("--gossip-subset", type=float, default=1.0,
                    metavar="FRAC",
                    help="partition-sampled gossip: ship only ceil(FRAC * "
                    "num_buckets) buckets per exchange on a deterministic "
                    "rotating schedule; unsent buckets skip (alpha=0). "
                    "Needs --packed when < 1.0")
    ap.add_argument("--wire-seed", type=int, default=0,
                    help="seed of the stochastic-rounding hash (independent "
                    "of --drop-seed)")
    ap.add_argument("--packed", action="store_true",
                    help="bucketed persistent-buffer gossip engine: params "
                    "packed once into LANE-aligned buckets, one ppermute + "
                    "in-place mix per bucket per step")
    ap.add_argument("--fused-update", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="single-sweep fused mix+apply update engine (one "
                    "HBM pass per bucket per step; default: on for --packed "
                    "runs, --no-fused-update restores the mix-then-apply "
                    "composition)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (--d-model, fp32, no remat) on the "
                    "--smoke-mesh")
    ap.add_argument("--smoke-mesh", default="1,1,1", metavar="POD,DATA,MODEL",
                    help="smoke-mesh axis sizes; pod>1 or data/model>1 need "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N set "
                    "before launch. With an fsdp-mode arch this exercises "
                    "the hierarchical shard-local packed engine on CPU "
                    "(gossip over pod, FSDP+TP over data/model)")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="restore from --checkpoint (if it exists) and "
                    "continue from its saved step; async runs resume their "
                    "inbox ring and gossip phase deterministically (a "
                    "checkpoint written at another --staleness is "
                    "mask-padded / truncated into this run's ring)")
    ap.add_argument("--log-every", type=int, default=10)
    return ap


def build_trainer(args: argparse.Namespace) -> tuple[Trainer, int]:
    """(trainer, start_step) for parsed launcher ``args``: config, mesh,
    step bundle, state placed with the step's shardings (restored from
    ``--checkpoint`` under ``--resume``) and the sharded token pipeline."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(
            reduced(cfg, d_model=args.d_model),
            param_dtype="float32", compute_dtype="float32")
        pod, data, model = (int(x) for x in args.smoke_mesh.split(","))
        mesh = make_smoke_mesh(data, model, pod=pod)
    else:
        mesh = make_device_mesh()
    dist = make_distribution(mesh, cfg.dist_mode)

    lr = step_decay(args.lr, 0.1, max(args.steps // 3, 1))
    if args.protocol == "agd":
        # Krizhevsky weak-scaling rule, AGD only (paper §7.1)
        lr = scale_lr_sqrt_p(lr, max(dist.dp, 1))
    opt = sgd(lr, momentum=0.9)

    state_shapes, state_axes, batch_shapes = train_input_specs(
        cfg, dist, args.seq_len, args.global_batch, opt)
    bundle = make_train_step_bundle(
        cfg, dist, opt, state_shapes=state_shapes, state_axes=state_axes,
        batch_shapes=batch_shapes, protocol=args.protocol,
        topology=args.topology, num_rotations=args.num_rotations,
        gossip_packed=args.packed, staleness=args.staleness,
        drop_rate=args.drop_timeout, drop_seed=args.drop_seed,
        wire_dtype=args.wire_dtype, gossip_subset=args.gossip_subset,
        wire_seed=args.wire_seed,
        fused_update=args.fused_update,
        remat=not args.smoke)
    # built under jit with the step's shardings, so each replica's state is
    # created on its own devices and never staged whole on device 0
    state = jax.jit(
        lambda key: init_train_state(
            key, cfg, dist, opt, packed=args.packed, layout=bundle.layout,
            inbox=bundle.protocol.staleness, wire=bundle.wire)[0],
        out_shardings=bundle.state_shardings)(jax.random.key(0))

    start_step = 0
    if args.resume and args.checkpoint and checkpoint_exists(args.checkpoint):
        meta = read_manifest(args.checkpoint).get("metadata", {})
        if meta.get("protocol") not in (None, args.protocol):
            raise SystemExit(
                f"checkpoint was written by protocol {meta['protocol']!r}; "
                f"refusing to resume it as {args.protocol!r}")
        state, manifest = restore_state(args.checkpoint, state)
        start_step = int(manifest.get("step") or 0)
        print(f"resumed {args.checkpoint} at step {start_step} "
              f"(phase {start_step % max(bundle.protocol.period, 1)})")

    ds = ShardedTokenDataset(cfg.vocab, args.seq_len,
                             n_shards=max(dist.dp, 1),
                             batch_per_shard=args.global_batch // max(dist.dp, 1))
    return Trainer(bundle, state, ds, log_every=args.log_every), start_step


def device_info() -> Dict:
    """The device the run used, as JAX reports it."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def run(argv: Optional[List[str]] = None) -> Dict:
    """Parse ``argv``, train, save ``--checkpoint``; return the summary."""
    args = build_parser().parse_args(argv)
    trainer, start_step = build_trainer(args)
    bundle = trainer.bundle
    hist = trainer.run(args.steps, start_step=start_step)
    if args.checkpoint:
        end_step = start_step + args.steps
        save_state(args.checkpoint, trainer.state,
                   metadata={"arch": bundle.cfg.name, "protocol": args.protocol,
                             "staleness": bundle.protocol.staleness,
                             "drop_timeout": args.drop_timeout,
                             "wire_dtype": args.wire_dtype,
                             "gossip_subset": args.gossip_subset,
                             "wire_seed": args.wire_seed,
                             "phase": end_step % max(bundle.protocol.period, 1)},
                   step=end_step)
        print(f"checkpoint -> {args.checkpoint}")
    device = device_info()
    paths = dict(bundle.attn_paths)
    if device["platform"] != "tpu":
        # the flash sites lower to the kernels on a TPU only
        paths = {"flash": 0, "dense": sum(paths.values())}
    return {"arch": bundle.cfg.name, "protocol": args.protocol,
            "final_loss": hist[-1]["loss"], "first_loss": hist[0]["loss"],
            "start_step": start_step, "device": device,
            "attn_paths": paths}


def main() -> None:
    setup_compile_cache()
    print(json.dumps(run()))


if __name__ == "__main__":
    main()
