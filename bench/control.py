#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 ...

For each seed, in one process: the program's first three steps through the
cell's own trainer against the float32 reference (the sound readings), then
in the program's place the reference computed at the precision below the
configuration's (the control), the reference with half of each batch left
out, and on more than one chip the reference with the exchange left out.
One JSON line per seed gives every gap; the limits in
``bench/limits/<cell>.json`` are set from these. Needs the cell's chips.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import cells, harness, reference  # noqa: E402


def readings(cell, seeds, *, log=print, changed_on=None):
    """Yield one dict of gaps per seed: the program's, and on the first
    ``changed_on`` seeds (all by default) the control's and the faults'."""
    import jax

    trainer, model = harness.build(cell)
    dp = max(trainer.bundle.dist.dp, 1)
    stated = cell.config["config"]["torch_dtype"]
    variants = {"control": {"quant": reference.LOWER[stated]},
                "half_batch": {"fault": "half_batch"}}
    if dp > 1:
        variants["no_exchange"] = {"fault": "no_exchange"}
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        fed = harness.seed_trainer(trainer, cell, model, seed)
        prog = harness.checked_steps(trainer)
        harness.free_state(trainer)
        prog = harness.read_checked(trainer, cell, model, seed, prog)
        gaps, ref = harness.check(cell, model, seed, fed, prog, dp)
        out = {"seed": seed, "program": {k: v[0] for k, v in gaps.items()},
               "where": {k: v[1] for k, v in gaps.items()},
               "loss": prog["loss"], "ref_loss": ref["loss"]}
        for name, kw in (variants.items()
                         if changed_on is None or i < changed_on else ()):
            other = reference.run(
                cell.config, cell.traffic, model.key_words(seed),
                [fed[s] for s in range(harness.N_CHECK_STEPS)],
                list(jax.devices())[:dp], **kw)
            out[name] = {k: v[0] for k, v in
                         reference.compare(other, ref).items()}
        out["seconds"] = time.perf_counter() - t
        log(json.dumps(out))
        yield out


def main():
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--changed-on", type=int, default=None,
                    help="run the control and the faults on the first N "
                    "seeds only (default: every seed)")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"control: no program under {ROOT / 'src'}")
    cell = cells.resolve(cells.load_benchmark(ROOT), args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.launch.cache import setup_compile_cache
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != cell.chips:
        sys.exit(f"control: {cell.name} needs {cell.chips} TPU chip(s), found "
                 f"{len(devs)} {devs[0].platform} device(s)")
    setup_compile_cache()
    for _ in readings(cell, args.seeds, changed_on=args.changed_on,
                      log=lambda s: print(s, flush=True)):
        pass
    print(f"control: done in {time.perf_counter() - T0:.1f} s", flush=True)


if __name__ == "__main__":
    main()
