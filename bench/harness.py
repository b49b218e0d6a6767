"""One run of one cell: set-up, warm-up, the measured window, the reference.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the trainer through the launcher's own ``build_parser`` and
``build_trainer``, replaces its state with weights made from ``--seed`` (one
jitted call, placed with the step's shardings) and its dataset with rows of
the traffic's token pool. The first three steps go through ``Trainer.run``
(the window's own call) and are what the reference checks, kept on the host
until the window's peak memory has been read; then every phase of the
gossip schedule has run once, and a few warm steps give the step time that
sizes the window. The window is one ``Trainer.run`` call of whole schedule
periods, timed until ``block_until_ready`` of the state returns.

The last line of standard output is the result, in JSON; earlier lines and
standard error say what was compared and how many compiles the window saw.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import time
from unittest import mock

from bench import cells, flops, peaks, reference, tracing

ROOT = cells.ROOT
TRACE_ROOT = ROOT / ".bench_trace"
WARM_STEPS = 4
N_CHECK_STEPS = 3


def _err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class _CompileCounter:
    """Counts JAX's compile-path events (tracing, lowering, compiling or a
    persistent-cache read) after ``arm``."""

    def __init__(self):
        import jax
        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, *_args, **_kw) -> None:
        if self.armed and (event.startswith("/jax/core/compile")
                           or event.startswith("/jax/compilation_cache")):
            self.count += 1


def _device_info() -> dict:
    import jax
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def _seeded_state(trainer, cell, model, key_data):
    """The trainer's state with the benchmark's seeded weights: the
    program's own ``init_train_state`` (packing, optimizer and any inbox),
    given ``model.init_params`` in place of its random init."""
    import jax
    from repro.train import step as step_mod

    bundle = trainer.bundle
    cfg = bundle.cfg
    box = {}

    def program_shapes():
        p, a = step_mod.lm_init(jax.random.key(0), cfg)
        box["axes"] = a
        return p

    want = jax.eval_shape(program_shapes)
    got = jax.eval_shape(lambda kd: model.init_params(cell.config, kd),
                         key_data)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{cell.config_name}: the configuration's weights "
                         "do not match the program's parameter tree")
    axes = box["axes"]

    def make(kd):
        seeded = lambda _key, _cfg: (model.init_params(cell.config, kd), axes)
        with mock.patch.object(step_mod, "lm_init", seeded):
            state, _ = step_mod.init_train_state(
                jax.random.key(0), cfg, bundle.dist, bundle.optimizer,
                packed=bundle.layout is not None, layout=bundle.layout,
                inbox=bundle.protocol.staleness, wire=bundle.wire)
        return state

    if not hasattr(trainer, "bench_init"):
        trainer.bench_init = jax.jit(make, out_shardings=bundle.state_shardings)
    return trainer.bench_init(key_data)


def _install_spans(trainer, spans: list):
    """Host spans around the trainer's input, dispatch and drain, kept in
    the profiler's trace (``bench.*``) and timed on the host clock."""
    import jax

    def spanned(name, fn, timed=None):
        def wrapper(*a, **kw):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                out = fn(*a, **kw)
            if timed is not None:
                timed.append(time.perf_counter() - t)
            return out
        return wrapper

    trainer.batch = spanned("bench.input", trainer.batch, spans)
    trainer._drain = spanned("bench.drain", trainer._drain)
    step_fn = trainer.step_fn
    trainer.step_fn = lambda phase: spanned("bench.dispatch", step_fn(phase))


def build(cell: cells.Cell):
    """(trainer, model): the launcher's trainer for the cell, its key(0)
    state freed."""
    from repro.launch import train as launcher

    model = reference.load_module("models", cell.config["reference"]["model"])
    args = launcher.build_parser().parse_args(cell.launcher_argv())
    trainer, _ = launcher.build_trainer(args)
    free_state(trainer)
    return trainer, model


def free_state(trainer) -> None:
    import jax
    for leaf in jax.tree.leaves(trainer.state):
        leaf.delete()
    trainer.state = None
    gc.collect()


def seed_trainer(trainer, cell: cells.Cell, model, seed: int) -> dict:
    """Give the trainer the seed's weights and token pool. Returns the dict
    into which the rows of the checked steps are recorded as they are fed."""
    import jax
    from repro.data import ShardedTokenDataset
    from bench.traffic.generator import TokenPool

    t = cell.traffic
    bundle = trainer.bundle
    dp = max(bundle.dist.dp, 1)
    trainer.state = _seeded_state(trainer, cell, model, model.key_words(seed))
    trainer.history = []
    trainer._inflight.clear()
    pool = TokenPool(bundle.cfg.vocab, seed, t["tokens"])
    trainer.dataset = ShardedTokenDataset(
        bundle.cfg.vocab, t["seq_len"], n_shards=dp,
        batch_per_shard=t["seqs_per_chip"], seed=seed, task=pool)
    fed = {}
    global_batch = trainer.dataset.global_batch

    def recording_global_batch(step):
        rows = global_batch(step)
        if step < N_CHECK_STEPS:
            fed[step] = rows.reshape(dp, -1, rows.shape[-1]).copy()
        return rows

    trainer.dataset.global_batch = recording_global_batch
    jax.block_until_ready(trainer.state)
    return fed


def _host_copy(tree):
    """(host arrays, shardings) of a tree of device arrays: the raw bytes,
    which ``jax.device_put(*copy)`` places again as they were."""
    import jax
    return jax.device_get(tree), jax.tree.map(lambda a: a.sharding, tree)


def checked_steps(trainer) -> dict:
    """Run steps 0..2 through ``Trainer.run`` (the window's own call and
    feed) and keep what the reference checks: each step's loss, and host
    copies of the momentum buckets after step 0 (the first gradient, as
    the optimizer got it) and of the parameter buckets after step 2. Only
    ``read_checked``, once the window's peak memory has been read, turns
    them into norms, so no reader of the check adds to ``peak_hbm_gb``."""
    trainer.run(1, start_step=0)
    mom = _host_copy(trainer.state["opt"]["mom"])
    trainer.run(N_CHECK_STEPS - 1, start_step=1)
    params = _host_copy(trainer.state["params"])
    return {"loss": [h["loss"] for h in trainer.history[:N_CHECK_STEPS]],
            "mom_copy": mom, "params_copy": params}


def read_checked(trainer, cell: cells.Cell, model, seed: int,
                 prog: dict) -> dict:
    """``prog`` with the per-leaf norms the reference compares: of the
    momentum after step 0 and of p_3 - p_0, with p_0 made again on the
    device from the seed. The copies go back to the device and are
    unpacked there; run it after ``free_state``."""
    import jax
    import jax.numpy as jnp

    if not hasattr(trainer, "bench_norms"):
        trainer.bench_norms = jax.jit(
            lambda mom: reference.replica_leaf_norms(mom.unpack()))
        trainer.bench_delta = jax.jit(
            lambda params, kd: reference.replica_leaf_norms(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b[None].astype(
                    jnp.float32),
                params.unpack(), model.init_params(cell.config, kd))))
    grad = jax.device_get(trainer.bench_norms(
        jax.device_put(*prog["mom_copy"])))
    delta = jax.device_get(trainer.bench_delta(
        jax.device_put(*prog["params_copy"]), model.key_words(seed)))
    return {"loss": prog["loss"], "grad": grad, "delta": delta}


def check(cell: cells.Cell, model, seed: int, fed: dict, prog: dict,
          dp: int):
    """(gaps, reference readings): the reference run on the fed rows, and
    ``prog`` compared with it."""
    import jax
    ref = reference.run(cell.config, cell.traffic, model.key_words(seed),
                        [fed[s] for s in range(N_CHECK_STEPS)],
                        list(jax.devices())[:dp])
    return reference.compare(prog, ref), ref


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool, *,
             t0: float, trace_dir: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    counter = _CompileCounter()
    t = cell.traffic

    t_build = time.perf_counter()
    trainer, model = build(cell)
    bundle = trainer.bundle
    dp = max(bundle.dist.dp, 1)
    fed = seed_trainer(trainer, cell, model, seed)
    build_s = time.perf_counter() - t_build
    _err(f"[bench] peak after build: {_device_info()['memory_peak_bytes']}")

    input_spans: list = []
    if trace:
        _install_spans(trainer, input_spans)

    t_warm = time.perf_counter()
    prog = checked_steps(trainer)
    period = max(bundle.protocol.period, 1)
    step = period * math.ceil(N_CHECK_STEPS / period)
    if step > N_CHECK_STEPS:        # the schedule's remaining phases
        trainer.run(step - N_CHECK_STEPS, start_step=N_CHECK_STEPS)
    jax.block_until_ready(trainer.state)
    warm_s = time.perf_counter() - t_warm
    _err(f"[bench] peak after checked steps: "
         f"{_device_info()['memory_peak_bytes']}")

    n_warm = period * math.ceil(WARM_STEPS / period)
    tw = time.perf_counter()
    trainer.run(n_warm, start_step=step)
    jax.block_until_ready(trainer.state)
    step_s = (time.perf_counter() - tw) / n_warm
    step += n_warm
    n = period * max(1, math.ceil(seconds / step_s / period))
    _err(f"[bench] {cell.name}: dp={dp}, period {period}, warm step "
         f"{step_s * 1e3:.3f} ms -> window of {n} steps")

    if trace:
        trace_dir = trace_dir or str(TRACE_ROOT / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    del input_spans[:]
    hist0 = len(trainer.history)
    gc.collect()
    gc.disable()
    counter.armed = True
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        t_window = time.perf_counter()
        trainer.run(n, start_step=step)
        jax.block_until_ready(trainer.state)
        window_s = time.perf_counter() - t_window
    counter.armed = False
    gc.enable()
    if trace:
        jax.profiler.stop_trace()
    setup_s = t_window - t0
    print(f"[bench] compiles inside the window: {counter.count}", flush=True)
    _err(f"[bench] window {n} steps in {window_s:.4f} s: "
         f"{window_s / n * 1e3:.3f} ms per step")
    window_losses = [h["loss"] for h in trainer.history[hist0:]]
    failed = sum(not math.isfinite(x) for x in window_losses)
    device = _device_info()
    tokens_per_step = t["seq_len"] * t["seqs_per_chip"]
    tps = n * tokens_per_step / window_s

    layout = bundle.layout
    moment_bytes = [jnp.dtype(layout.bucket_dtypes[0]).itemsize
                    for _ in bundle.optimizer.fused_moments]
    record = {
        "cell": cell.name, "chips": cell.chips, "steps": n,
        "window_s": window_s, "tokens_per_s_per_chip": tps,
        "setup_build_s": build_s, "setup_warm_s": warm_s,
        "input_spans_s": list(input_spans),
        "flops_per_token": flops.train_flops_per_token(
            **_model_dims(model, cell.config), seq_len=t["seq_len"]),
        "peaks": peaks.peaks(device["kind"]) if device["platform"] == "tpu"
        else None,
        "update": {
            "bucket_elems": list(layout.bucket_sizes),
            "param_bytes": jnp.dtype(layout.bucket_dtypes[0]).itemsize,
            "grad_bytes": jnp.dtype(layout.bucket_dtypes[0]).itemsize,
            "moment_bytes": moment_bytes,
            "partner": dp > 1 and bool(bundle.fused)
            and reference.hyper(t)["alpha"] != 0.0},
        "trace": None,
    }

    # free the program's state before the reference runs
    free_state(trainer)

    if trace:
        record["trace"] = tracing.load(trace_dir)
        device["busy_s"] = tracing.mean_busy_s(record["trace"])
        w0, w1 = record["trace"].window()
        device["window_s"] = w1 - w0
        if trace_dir.startswith(str(TRACE_ROOT)):
            shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    prog = read_checked(trainer, cell, model, seed, prog)
    gaps, ref = check(cell, model, seed, fed, prog, dp)
    _err(f"[bench] reference {time.perf_counter() - t_ref:.1f} s")
    correct = reference.judge(gaps, cell.limits) and failed == 0
    _err(f"[bench] losses program {prog['loss']} reference {ref['loss']}")

    metrics = {}
    chosen = cell.per_layer if trace else cell.end_to_end
    e2e = {"setup_s": setup_s, "tokens_per_s_per_chip": tps,
           "peak_hbm_gb": device["memory_peak_bytes"] / 1e9}
    for m in chosen:
        value = (cells.metric_reader(m["name"])(record) if trace
                 else e2e.get(m["name"]))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": n, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {
            "device_ops": tracing.top_ops(record["trace"]),
            "idle_gaps": tracing.top_gaps(record["trace"])}
    checks = {k: {"value": gaps[k][0], "limit": cell.limits[k],
                  "worst": gaps[k][1]} for k in reference.LIMIT_KEYS}
    result["checks"] = checks
    for k, c in checks.items():
        limit = ("not compared" if c["limit"] is None
                 else f"limit {c['limit']!r}")
        _err(f"check {k} {c['value']!r} {limit} ({c['worst']})")
    return result


def _model_dims(model, config: dict) -> dict:
    m = model.dims(config)
    return {"d": m["d"], "layers": m["layers"], "heads": m["heads"],
            "kv_heads": m["kv_heads"], "head_dim": m["head_dim"],
            "ff": m["ff"], "vocab": m["vocab"]}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a directory "
                    "inside the checkout, removed once read)")
    return ap.parse_args(argv)


def main(argv, t0: float) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        _err(f"bench: no program under {ROOT / 'src'}: run from a checkout")
        return 2
    cell = cells.resolve(cells.load_benchmark(ROOT), args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        _err(f"bench: no TPU: JAX found {len(devs)} {devs[0].platform} "
             "device(s)")
        return 2
    if len(devs) != cell.chips:
        _err(f"bench: {cell.name} needs {cell.chips} chip(s), found "
             f"{len(devs)}")
        return 2
    from repro.launch.cache import setup_compile_cache
    _err(f"[bench] compile cache {setup_compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t0=t0, trace_dir=args.trace_dir)
    print(json.dumps(result), flush=True)
    return 0
