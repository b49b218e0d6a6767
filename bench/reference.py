"""The comparison that decides ``correct`` for a training cell.

The reference follows the program's first three steps from the same seeded
weights and the same rows: the plain float32 model of
``bench/models/<model>.py`` and the exchange and update of
``bench/protocols/<protocol>.py``, one replica per device. Three numbers are
compared, each against its limit in ``bench/limits/<cell>.json``:

- ``loss_gap``: the largest |loss - reference loss| / reference loss over
  steps 0, 1, 2 (the loss averaged over replicas, as the program logs it);
- ``grad_gap``: the first gradient as the optimizer got it (the momentum
  after step 0), worst leaf of any replica: |norm - reference norm| over
  the larger of that leaf's reference norm and the median leaf's;
- ``delta_gap``: the parameters' change after three steps, p_3 - p_0, by
  the same measure, over the leaves whose reference gradient norm is at
  least 1/1000 of the median leaf's (a leaf under that moves by round-off).

``quant`` (a lower operand precision) and ``fault`` (``half_batch``: the
loss over the first half of each replica's rows; ``no_exchange``: the gossip
mix left out) put a changed reference in the program's place; the control
and the fault readings come from them.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
LIMIT_KEYS = ("loss_gap", "grad_gap", "delta_gap")
# the precision below each stated one (the control's)
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}
MOVED = 1e-3


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded once per process (its jitted
    functions then compile once)."""
    key = f"bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, BENCH / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


@functools.lru_cache(maxsize=8)
def _grad_fn(config_json: str, quant):
    """The jitted loss-and-gradient of one configuration and precision.
    Differentiated w.r.t. the stored parameters: the float32 gradient is
    rounded once, to the parameters' dtype, as an optimizer receives it."""
    config = json.loads(config_json)
    model = load_module("models", config["reference"]["model"])
    return jax.jit(jax.value_and_grad(
        lambda p, tokens: model.loss_fn(p, tokens, config, quant=quant)))


def hyper(traffic: dict) -> dict:
    """The update's settings: the learning rate and schedule from the
    traffic's launcher options, momentum and alpha from the protocol's
    reference (the launcher takes neither as an option)."""
    opts = traffic["launcher"]
    proto = load_module("protocols", traffic["reference"]["protocol"])
    return {"lr": float(opts["--lr"]), "momentum": proto.MOMENTUM,
            "alpha": proto.ALPHA,
            "topology": opts["--topology"],
            "num_rotations": int(opts["--num-rotations"])}


def leaf_norms(tree) -> dict:
    """{leaf path: float32 norm} of one replica's tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in flat}


def replica_leaf_norms(tree) -> dict:
    """{leaf path: (dp,) norms} of a tree whose leaves lead with replicas."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)).reshape(v.shape[0], -1), axis=1))
        for k, v in flat}


def run(config: dict, traffic: dict, key_data, batches, devices, *,
        quant=None, fault=None) -> dict:
    """Readings of the reference over ``batches`` (steps 0..2, each a
    (dp, rows, S+1) host array): losses, first-gradient and 3-step change
    norms per leaf, each a (dp,) array."""
    model = load_module("models", config["reference"]["model"])
    proto = load_module("protocols", traffic["reference"]["protocol"])
    hp = hyper(traffic)
    if fault == "no_exchange":
        hp = dict(hp, alpha=0.0)
    dp = len(devices)

    grad_fn = _grad_fn(json.dumps(config, sort_keys=True), quant)
    init = jax.jit(functools.partial(model.init_params, config))
    params = [jax.device_put(init(key_data), d) for d in devices]
    moms = [jax.tree.map(jnp.zeros_like, p) for p in params]
    losses, g0 = [], None
    for step, host in enumerate(batches):
        host = np.asarray(host)
        if fault == "half_batch":
            host = host[:, : max(host.shape[1] // 2, 1)]
        out = [grad_fn(params[r], jax.device_put(host[r], devices[r]))
               for r in range(dp)]
        losses.append(float(np.mean([float(v) for v, _ in out])))
        grads = [g for _, g in out]
        if step == 0:
            g0 = [jax.device_get(leaf_norms(g)) for g in grads]
        params, moms = proto.step_update(step, params, moms, grads, hp=hp,
                                         devices=devices)
        del out, grads
    del moms
    delta = jax.jit(lambda p, kd: leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        p, model.init_params(config, kd))))
    d3 = [jax.device_get(delta(params[r], jax.device_put(key_data,
                                                          devices[r])))
          for r in range(dp)]
    del params
    stack = lambda rs: {k: np.array([float(r[k]) for r in rs]) for k in rs[0]}
    return {"loss": losses, "grad": stack(g0), "delta": stack(d3)}


def _worst(prog: dict, ref: dict, keep=None):
    """Worst leaf of |prog norm - ref norm| / max(ref norm, median ref norm
    of that replica); returns (gap, leaf path)."""
    keys = [k for k in ref if keep is None or keep[k].all()]
    worst, where = 0.0, ""
    dp = len(next(iter(ref.values())))
    for r in range(dp):
        med = float(np.median([ref[k][r] for k in ref]))
        for k in keys:
            gap = abs(float(prog[k][r]) - float(ref[k][r])) / max(
                float(ref[k][r]), med, 1e-30)
            if not np.isfinite(gap):
                gap = float("inf")
            if gap > worst or not where:
                worst, where = gap, f"{k}[{r}]"
    return worst, where


def compare(prog: dict, ref: dict) -> dict:
    """{number: (value, where)} of the program's readings against the
    reference's."""
    loss = max(abs(p - r) / abs(r) if np.isfinite(p) else float("inf")
               for p, r in zip(prog["loss"], ref["loss"]))
    gmed = {}
    dp = len(next(iter(ref["grad"].values())))
    for r in range(dp):
        gmed[r] = float(np.median([v[r] for v in ref["grad"].values()]))
    keep = {k: np.array([v[r] >= MOVED * gmed[r] for r in range(dp)])
            for k, v in ref["grad"].items()}
    return {"loss_gap": (loss, "steps 0-2"),
            "grad_gap": _worst(prog["grad"], ref["grad"]),
            "delta_gap": _worst(prog["delta"], ref["delta"], keep)}


def judge(gaps: dict, limits: dict) -> bool:
    """Every number with a limit within it; a limit of null marks a number
    that no control or fault separates from sound runs in that cell, which
    is reported and not compared."""
    return all(gaps[k][0] <= limits[k] for k in LIMIT_KEYS
               if limits[k] is not None)
