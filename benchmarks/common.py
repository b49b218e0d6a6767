"""Shared benchmark substrate: a small-but-real LM trained on the learnable
bigram task with p simulated replicas (vmapped) — the laptop-scale analogue
of the paper's LeNet3/MNIST + CIFARNet/CIFAR10 experiments, per the repro
band ("pure-algorithm build fully works at laptop scale")."""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import re

from repro.configs import get_config
from repro.core import (build_schedule, init_inbox_ring,
                        make_async_sim_train_step, make_sim_train_step,
                        replicate)
from repro.data import BigramTaskDataset
from repro.models import lm_init, reduced
from repro.optim import sgd
from repro.train import make_loss_fn

# v5e constants (same as launch.roofline)
HBM = 819e9
ICI = 50e9


def tiny_lm_cfg(d_model=64, vocab=128):
    cfg = reduced(get_config("qwen3-0.6b"), d_model=d_model, vocab=vocab)
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")


_WIRE_SUFFIXES = {"8": "int8", "f8": "fp8", "b16": "bf16"}


def parse_async_protocol(protocol: str):
    """``gossip_async[_k<K>][_drop<PCT>][_q<WIRE>][_sub<PCT>]`` ->
    (staleness, drop_rate, wire_dtype, gossip_subset) or None for non-async
    protocols — the bounded-delay sweep naming used by the ablation /
    straggler / wire benches and examples/gossip_vs_agd.py.  Examples:

        gossip_async_k4_drop30   staleness-4 ring, 30% injected drops
        gossip_async_k2_q8       staleness-2, int8 stochastic-rounded wire
        gossip_async_qf8_sub50   fp8-e4m3 wire, 50% partition-sampled buckets
        gossip_async_k4_q8_sub50 all of the above combined

    ``_q8`` -> int8, ``_qf8`` -> fp8, ``_qb16`` -> bf16 (no suffix = fp32);
    ``_sub<PCT>`` -> gossip_subset = PCT / 100."""
    m = re.fullmatch(r"gossip_async(?:_k(\d+))?(?:_drop(\d+))?"
                     r"(?:_q(8|f8|b16))?(?:_sub(\d+))?", protocol)
    if not m:
        return None
    return (int(m.group(1) or 1), int(m.group(2) or 0) / 100.0,
            _WIRE_SUFFIXES.get(m.group(3), "fp32"),
            int(m.group(4) or 100) / 100.0)


def make_replica_lm(p: int, protocol: str, *, lr=0.3, seed=0,
                    num_rotations=2, d_model=64, vocab=128):
    """``gossip_async*`` protocols (see ``parse_async_protocol``) use the
    bounded-delay step (core.simulate.make_async_sim_train_step):
    step(opt_state, params, ring, batch, t); every other protocol keeps the
    4-arg synchronous step."""
    cfg = tiny_lm_cfg(d_model, vocab)
    params, _ = lm_init(jax.random.key(seed), cfg)
    loss_fn_full = make_loss_fn(cfg)
    loss_fn = lambda prms, batch: loss_fn_full(prms, batch)[0]
    sched = build_schedule(max(p, 2), num_rotations=num_rotations, seed=seed)
    opt = sgd(lr, momentum=0.9)
    async_kd = parse_async_protocol(protocol)
    if async_kd is not None:
        k, drop, wire_dtype, subset = async_kd
        step = make_async_sim_train_step(loss_fn, opt, sched, staleness=k,
                                         drop_rate=drop, drop_seed=seed,
                                         wire_dtype=wire_dtype,
                                         gossip_subset=subset,
                                         wire_seed=seed)
    else:
        step = make_sim_train_step(loss_fn, opt, sched, protocol=protocol)
    params = replicate(params, p)
    opt_state = opt.init(params)
    return cfg, step, params, opt_state, sched


def run_replica_lm(p: int, protocol: str, steps: int, *, seq_len=32,
                   batch_per_replica=4, lr=0.3, seed=0,
                   time_budget_s: float | None = None
                   ) -> Tuple[List[Dict], float]:
    """Returns (history, wall_seconds). Batches come from p distinct bigram
    shards with ring rotation (the paper's sample shuffle)."""
    cfg, step, params, opt_state, sched = make_replica_lm(
        p, protocol, lr=lr, seed=seed)
    task = BigramTaskDataset(cfg.vocab, seed=seed + 991)
    async_kd = parse_async_protocol(protocol)
    is_async = async_kd is not None
    inbox = init_inbox_ring(params, async_kd[0], p) if is_async else None

    def batch_for(t):
        toks = np.stack([
            task.sample(np.random.default_rng(
                ((seed * 7 + ((r - t) % p)) * 1_000_003 + t)),
                batch_per_replica, seq_len + 1)
            for r in range(p)])
        return {"tokens": jnp.asarray(toks)}

    def one(t, opt_state, params, inbox):
        if is_async:
            opt_state, params, inbox, m = step(opt_state, params, inbox,
                                               batch_for(t), jnp.int32(t))
        else:
            opt_state, params, m = step(opt_state, params, batch_for(t),
                                        jnp.int32(t))
        return opt_state, params, inbox, m

    hist = []
    # warm up compile outside the timed region
    opt_state, params, inbox, m = one(0, opt_state, params, inbox)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for t in range(1, steps):
        opt_state, params, inbox, m = one(t, opt_state, params, inbox)
        hist.append({k: float(v) for k, v in m.items()} | {"step": t})
        if time_budget_s and time.perf_counter() - t0 > time_budget_s:
            break
    jax.block_until_ready(jax.tree.leaves(params)[0])
    wall = time.perf_counter() - t0
    return hist, wall
