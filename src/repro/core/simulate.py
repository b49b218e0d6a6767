"""Single-process p-replica simulator for GossipGraD protocols.

Replicates the distributed semantics on one device by carrying an explicit
leading *replica* axis on every parameter/batch leaf and implementing the
communication primitives as gathers over that axis:

    ppermute(x, recv_from)  ==  x[recv_from]
    psum(x, data_axis)      ==  x.sum(0) broadcast back

This serves two purposes:

1. **oracle** — the shard_map/ppermute implementation in gossip.py must match
   this simulator step-for-step (tested with 8 forced host devices);
2. **laptop-scale science** — the paper's convergence-equivalence experiments
   (Figs 12–14, 17) run here: p replicas of a real model trained with
   gossip / AGD / every-log(p) / no-comm on one CPU, via a single vmapped
   gradient computation per step.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.quantize import (LANE, WireFormat, decode_wire,
                                    encode_wire, wire_key)

from .topology import GossipSchedule, build_subset_schedule

PyTree = Any

__all__ = [
    "replicate",
    "gossip_mix_sim",
    "gossip_mix_sim_delayed",
    "gossip_mix_sim_delayed_k",
    "gossip_mix_sim_quantized",
    "gossip_mix_sim_quantized_k",
    "allreduce_mean_sim",
    "replica_variance",
    "make_sim_train_step",
    "make_async_sim_train_step",
]


def replicate(params: PyTree, p: int) -> PyTree:
    """Tile every leaf with a leading replica axis of size p."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (p,) + x.shape), params)


def gossip_mix_sim(params: PyTree, recv_from: jnp.ndarray) -> PyTree:
    """w_j <- (w_j + w_{recv_from[j]}) / 2 over the leading replica axis."""
    return jax.tree.map(lambda x: (x + x[recv_from]) * 0.5, params)


def gossip_mix_sim_delayed(params: PyTree, inbox: PyTree,
                           recv_from: jnp.ndarray, alpha: float = 0.5
                           ) -> Tuple[PyTree, PyTree]:
    """Delayed-mix oracle for the staleness-1 async protocol (§5).

    One async step at schedule row ``recv_from``: the arrival mix consumes
    the inbox (data exchanged one step earlier), then the outgoing exchange
    of the freshly mixed params is performed eagerly — in the distributed
    implementation (core.async_gossip) that ppermute is in flight during the
    next step's compute and lands as its inbox.

        mixed_j     = (1-alpha) * params_j + alpha * inbox_j
        new_inbox_j = mixed_{recv_from[j]}

    A fresh run bootstraps with ``inbox = params`` ("nothing received yet"),
    making the first arrival mix the identity. The shard_map implementation
    must match this function bit-exactly (tests/test_async_gossip.py).
    """
    mixed = jax.tree.map(lambda x, b: x * (1.0 - alpha) + b * alpha,
                         params, inbox)
    new_inbox = jax.tree.map(lambda m: m[recv_from], mixed)
    return mixed, new_inbox


def gossip_mix_sim_delayed_k(params: PyTree, ring: Any,
                             recv_from: jnp.ndarray, alpha: float = 0.5,
                             ok: jnp.ndarray = None
                             ) -> Tuple[PyTree, Any]:
    """Bounded-delay oracle for the staleness-k inbox ring (§4.2/§5) — the
    reference semantics of core.async_gossip's shard_map engines.

    ``ring`` is an ``init_inbox_ring`` structure: ``slots`` (k param-shaped
    trees, oldest first), ``valid`` ((p, k) landed/valid mask) and ``t``
    (dispatch counter). One async step at schedule row ``recv_from``:

        a_eff_j     = alpha * valid[j, 0]          (masked alpha — the
                                                    gossip_mix_sim_masked
                                                    weighting, generalized)
        mixed_j     = (1 - a_eff_j) * params_j + a_eff_j * slots[0]_j
        payload_j   = mixed_{recv_from[j]}         (lands k steps later)
        ring'       = slots[1:] + [payload],  valid' = [valid[:, 1:], ok]

    A skipped/dropped exchange (valid 0) mixes with alpha = 0 — the mixing
    matrix row degenerates to the identity row but still sums to 1
    (row-stochastic), so a consensus state is a fixed point under any drop
    pattern and, with no drops, the replica mean is preserved exactly (the
    doubly stochastic (1-a)I + aP case).  ``ok`` is this dispatch's
    landed-flag per receiving rank (``core.async_gossip.exchange_ok``;
    defaults to all-ones).  At staleness 1 with an all-valid mask this is
    exactly ``gossip_mix_sim_delayed``.  The shard_map implementation must
    match this function bit-exactly (tests/test_async_gossip.py).
    """
    slots, valid, t = ring["slots"], ring["valid"], ring["t"]
    a = alpha * valid[:, 0]

    def mix(x, b):
        w = a.reshape(a.shape + (1,) * (x.ndim - 1))
        return x * (1.0 - w) + b * w

    mixed = jax.tree.map(mix, params, slots[0])
    payload = jax.tree.map(lambda m: m[recv_from], mixed)
    if ok is None:
        ok = jnp.ones((valid.shape[0],), jnp.float32)
    new_ring = {
        "slots": tuple(slots[1:]) + (payload,),
        "valid": jnp.concatenate(
            [valid[:, 1:], ok.astype(jnp.float32)[:, None]], axis=1),
        "t": t + 1,
    }
    return mixed, new_ring


def gossip_mix_sim_quantized(buckets, recv_from: jnp.ndarray, t, *,
                             wire: WireFormat, alpha: float = 0.5):
    """Quantized-wire oracle for the SYNCHRONOUS packed engines — the
    reference semantics of ``core.gossip.make_packed_gossip_mix(wire=...)``
    (and, composed with the optimizer algebra, the fused twin).

    ``buckets`` is the global view: a list of ``(p, rows, 128)`` arrays,
    one per layout bucket, each entry of the leading axis one replica's
    bucket in its storage shape.  One
    exchange at dispatch step ``t``, schedule row ``recv_from``:

        enc_j     = encode_wire(x_j, keyed on (t, rank=j, bucket, seed))
        payload_j = enc_{recv_from[j]}              (codes AND scales move)
        mixed_j   = (1-alpha) * x_j + alpha * dequant(payload_j)

    with the decode FOLDED into the mix expression — one traced computation,
    exactly what the in-kernel (column-stream scale) decode contracts to —
    and buckets outside the rotating subset at step ``t`` passed through
    untouched.  Shard-local (fsdp) layouts agree bit-for-bit because the
    engine keys noise by the GLOBAL element index (``base_index``) and
    128-tiles never straddle shard boundaries (strides are LANE multiples).

    ``t`` may be a static Python int (subset skip resolved statically, like
    the engine) or a traced scalar (subset applied by ``jnp.where`` — the
    same bits either way).
    """
    subset = build_subset_schedule(len(buckets), wire.subset)
    p = int(buckets[0].shape[0])
    ranks = jnp.arange(p)
    static_t = isinstance(t, (int, np.integer))
    sel = subset.selected(int(t)) if (static_t and subset is not None) \
        else None
    mask = subset.mask(t) if (not static_t and subset is not None) else None
    out = []
    for i, x in enumerate(buckets):
        if sel is not None and not sel[i]:
            out.append(x)
            continue
        keys = wire_key(t, ranks, i, wire.seed)
        enc = encode_wire(x, wire.dtype, keys=keys)
        payload = jax.tree.map(lambda e: e[recv_from], enc)
        b = decode_wire(payload)
        mixed = (x.astype(jnp.float32) * (1.0 - alpha)
                 + b.astype(jnp.float32) * alpha).astype(x.dtype)
        if mask is not None:
            mixed = jnp.where(mask[i], mixed, x)
        out.append(mixed)
    return out


def gossip_mix_sim_quantized_k(buckets, ring: Any, recv_from: jnp.ndarray, *,
                               wire: WireFormat, alpha: float = 0.5,
                               ok: jnp.ndarray = None):
    """Quantized-wire oracle for the staleness-k ASYNC ring — the reference
    semantics of ``core.async_gossip.make_packed_async_gossip_mix(wire=...)``.

    ``ring`` is an ``init_wire_inbox_ring`` structure over GLOBAL buckets:
    each slot a tuple of per-bucket wire payloads (codes ``(p, rows, 128)``
    + scales ``(p, rows)`` when quantized), oldest first.  One step:

        a_eff_j = alpha * valid[j, 0]
        mixed_j = (1-a_eff_j) * x_j + a_eff_j * dequant(slots[0]_j)
                    for buckets in the CONSUMPTION subset selected(t - k)
                    (the consumed slot was dispatched k steps ago);
                  x_j untouched otherwise
        dispatch: encode the mixed bucket (keys on the ring counter ``t``),
                  gather by ``recv_from``; buckets outside selected(t)
                  append an all-zero payload
        ring'   = FIFO advance with landed-flag ``ok``

    The decode is folded into the mix expression (the in-sweep kernel
    contract) and the subset masks use the floor-mod ``mask(t)`` twin, so
    the first k bootstrap steps (negative ``t - k``) agree with the
    engines' static ``selected(phase - k)`` selection.
    """
    subset = build_subset_schedule(len(buckets), wire.subset)
    slots, valid, t = ring["slots"], ring["valid"], ring["t"]
    k = len(slots)
    p = int(buckets[0].shape[0])
    ranks = jnp.arange(p)
    a = alpha * valid[:, 0]
    sel_cons = subset.mask(t - k) if subset is not None else None
    sel_send = subset.mask(t) if subset is not None else None
    mixed_buckets = []
    for i, x in enumerate(buckets):
        b = decode_wire(slots[0][i])
        w = a.reshape((p,) + (1,) * (x.ndim - 1))
        mix = (x.astype(jnp.float32) * (1.0 - w)
               + b.astype(jnp.float32) * w).astype(x.dtype)
        if sel_cons is not None:
            mix = jnp.where(sel_cons[i], mix, x)
        mixed_buckets.append(mix)
    payload = []
    for i, m in enumerate(mixed_buckets):
        enc = encode_wire(m, wire.dtype, keys=wire_key(t, ranks, i,
                                                       wire.seed))
        gathered = jax.tree.map(lambda e: e[recv_from], enc)
        if sel_send is not None:
            gathered = jax.tree.map(
                lambda g: jnp.where(sel_send[i], g, jnp.zeros_like(g)),
                gathered)
        payload.append(gathered)
    if ok is None:
        ok = jnp.ones((valid.shape[0],), jnp.float32)
    new_ring = {
        "slots": tuple(slots[1:]) + (tuple(payload),),
        "valid": jnp.concatenate(
            [valid[:, 1:], ok.astype(jnp.float32)[:, None]], axis=1),
        "t": t + 1,
    }
    return mixed_buckets, new_ring


def allreduce_mean_sim(params: PyTree) -> PyTree:
    """All ranks replaced by the replica mean (one all-reduce)."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x.mean(0, keepdims=True), x.shape), params
    )


def replica_variance(params: PyTree) -> jnp.ndarray:
    """Mean squared deviation of replicas from the replica mean — the
    'model drift' the paper's diffusion argument keeps bounded."""
    leaves = jax.tree.leaves(params)
    tot = 0.0
    n = 0
    for x in leaves:
        mu = x.mean(0, keepdims=True)
        tot = tot + jnp.sum((x - mu) ** 2)
        n += x.size
    return tot / n


def gossip_mix_sim_masked(params: PyTree, recv_from: jnp.ndarray,
                          ok: jnp.ndarray) -> PyTree:
    """Gossip mix where exchange i only happens if ok[i] (rank-failure /
    message-loss model: a failed exchange leaves the local model unchanged —
    the paper's 'each exchange is not expected to be reliable' premise,
    §4.2)."""
    m = ok.astype(jnp.float32)

    def mix(x):
        shape = (len(m),) + (1,) * (x.ndim - 1)
        w = m.reshape(shape) * 0.5
        return x * (1.0 - w) + x[recv_from] * w

    return jax.tree.map(mix, params)


def make_sim_train_step(
    loss_fn: Callable[[PyTree, Any], jnp.ndarray],
    optimizer,
    schedule: GossipSchedule,
    protocol: str = "gossip",
    drop_prob: float = 0.0,
    seed: int = 0,
) -> Callable:
    """Build a jitted p-replica simulated train step.

    loss_fn(params, batch) -> scalar, for ONE replica. Batches carry a leading
    replica axis. Returns step(opt_state, params_rep, batch_rep, step_idx) ->
    (opt_state, params_rep, metrics).

    Protocols (paper Table 6 + §4.1/§7.5 + ablations):
      gossip      — local update then pairwise mix with the step's partner
                    (THE paper's algorithm);
      gossip_grad — gradients (not models) averaged with the partner before
                    the update — the Blot/Jin-style variant the paper argues
                    against (ablation);
      agd         — gradients mean-all-reduced every step (baseline);
      every_logp  — all-reduce of *models* every log2(p) steps, else local;
      none        — no communication (the rejected ensemble extreme, §4.1).

    ``drop_prob`` > 0 drops individual gossip exchanges at random (rank
    failure / unreliable-message ablation); only meaningful for gossip*.
    """
    p = schedule.p
    perm_table = jnp.asarray(
        np.stack([schedule.recv_from(t) for t in range(schedule.period)])
    )
    grad_fn = jax.vmap(jax.value_and_grad(loss_fn))
    base_key = jax.random.key(seed + 7919)

    @jax.jit
    def step(opt_state, params, batch, step_idx):
        losses, grads = grad_fn(params, batch)
        recv = perm_table[step_idx % schedule.period]
        if drop_prob > 0.0:
            ok = jax.random.uniform(
                jax.random.fold_in(base_key, step_idx), (p,)) >= drop_prob
        else:
            ok = jnp.ones((p,), bool)
        if protocol == "agd":
            grads = jax.tree.map(
                lambda g: jnp.broadcast_to(g.mean(0, keepdims=True), g.shape), grads
            )
        elif protocol == "gossip_grad":
            grads = gossip_mix_sim_masked(grads, recv, ok)
        params, opt_state = optimizer.update(params, grads, opt_state)
        if protocol == "gossip":
            params = gossip_mix_sim_masked(params, recv, ok)
        elif protocol == "every_logp":
            params = jax.lax.cond(
                (step_idx + 1) % schedule.substeps == 0,
                allreduce_mean_sim,
                lambda q: q,
                params,
            )
        elif protocol in ("agd", "none", "gossip_grad"):
            pass
        else:
            raise ValueError(f"unknown protocol {protocol!r}")
        metrics = {
            "loss": losses.mean(),
            "replica_variance": replica_variance(params),
        }
        return opt_state, params, metrics

    return step


def make_async_sim_train_step(
    loss_fn: Callable[[PyTree, Any], jnp.ndarray],
    optimizer,
    schedule: GossipSchedule,
    alpha: float = 0.5,
    staleness: int = 1,
    drop_rate: float = 0.0,
    drop_seed: int = 0,
    wire_dtype: str = "fp32",
    gossip_subset: float = 1.0,
    wire_seed: int = 0,
) -> Callable:
    """Jitted p-replica simulated train step for the bounded-delay async
    protocol — the laptop-scale twin of the ``gossip_async`` train step.

    Mirrors the distributed program structure exactly (arrival mix first,
    then compute), so given the same batches it produces the same loss
    sequence as the sharded trainer:

        step(opt_state, params, ring, batch_rep, step_idx)
            -> (opt_state, params, ring, metrics)

    Start with ``ring = core.async_gossip.init_inbox_ring(params,
    staleness, p)`` (the bounded-delay bootstrap: nothing received yet, the
    first ``staleness`` arrival mixes are skips).  ``drop_rate`` injects
    the emulated-wire timeout drops through the same ``exchange_ok`` hash
    the distributed engines use, so sim and shard_map trajectories stay
    bit-identical.  ``metrics['replica_variance']`` is measured at the
    mixed params — the model drift the paper's diffusion argument keeps
    bounded.

    ``wire_dtype`` / ``gossip_subset`` / ``wire_seed`` turn on the
    SCIENCE-MODE compressed wire: this is the drift/final-loss twin of the
    ISSUE's wire knobs, not a bit-exactness oracle (those are the
    ``gossip_mix_sim_quantized*`` functions over real bucket layouts).
    Each param LEAF is treated as one wire bucket (zero-padded to a LANE
    multiple for the per-tile scales), the outgoing mixed leaf goes through
    an encode->decode roundtrip before landing in the ring — the slots keep
    holding param-shaped fp32 trees, which is equivalent because decoding
    at dispatch or at arrival is the same arithmetic — and leaves outside
    the rotating subset ship zeros and are consumed at alpha = 0.  The
    default (fp32, subset 1.0) is the exact PR-4 code path.
    """
    from .async_gossip import exchange_ok

    p = schedule.p
    ranks = jnp.arange(p)
    perm_table = jnp.asarray(
        np.stack([schedule.recv_from(t) for t in range(schedule.period)])
    )
    grad_fn = jax.vmap(jax.value_and_grad(loss_fn))
    wire = WireFormat(dtype=wire_dtype, subset=gossip_subset, seed=wire_seed)

    if wire.is_default:
        @jax.jit
        def step(opt_state, params, ring, batch, step_idx):
            assert len(ring["slots"]) == int(staleness), (
                f"ring carries {len(ring['slots'])} slots but the step was "
                f"built for staleness {staleness}")
            recv = perm_table[step_idx % schedule.period]
            ok = exchange_ok(ring["t"], ranks, drop_seed, drop_rate)
            mixed, new_ring = gossip_mix_sim_delayed_k(params, ring, recv,
                                                       alpha, ok)
            losses, grads = grad_fn(mixed, batch)
            new_params, opt_state = optimizer.update(mixed, grads, opt_state)
            metrics = {
                "loss": losses.mean(),
                "replica_variance": replica_variance(mixed),
            }
            return opt_state, new_params, new_ring, metrics

        return step

    def _roundtrip(m, t, leaf_idx):
        """encode->decode one (p, ...) leaf through the wire format."""
        if wire.dtype == "bf16":
            return m.astype(jnp.bfloat16).astype(m.dtype)
        flat = m.reshape(p, -1).astype(jnp.float32)
        n = flat.shape[1]
        pad = (-n) % LANE
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        keys = wire_key(t, ranks, leaf_idx, wire.seed)
        dec = decode_wire(encode_wire(flat.reshape(p, -1, LANE), wire.dtype,
                                      keys=keys)).reshape(p, -1)
        if pad:
            dec = dec[:, :n]
        return dec.reshape(m.shape).astype(m.dtype)

    @jax.jit
    def step(opt_state, params, ring, batch, step_idx):
        assert len(ring["slots"]) == int(staleness), (
            f"ring carries {len(ring['slots'])} slots but the step was "
            f"built for staleness {staleness}")
        recv = perm_table[step_idx % schedule.period]
        slots, valid, t = ring["slots"], ring["valid"], ring["t"]
        ok = exchange_ok(t, ranks, drop_seed, drop_rate)
        a = alpha * valid[:, 0]
        leaves, treedef = jax.tree.flatten(params)
        slot_leaves = jax.tree.leaves(slots[0])
        subset = build_subset_schedule(len(leaves), wire.subset)
        sel_cons = (subset.mask(t - int(staleness))
                    if subset is not None else None)
        sel_send = subset.mask(t) if subset is not None else None
        mixed_leaves = []
        for i, (x, b) in enumerate(zip(leaves, slot_leaves)):
            w = a.reshape((p,) + (1,) * (x.ndim - 1))
            mix = x * (1.0 - w) + b * w
            if sel_cons is not None:
                mix = jnp.where(sel_cons[i], mix, x)
            mixed_leaves.append(mix)
        mixed = jax.tree.unflatten(treedef, mixed_leaves)
        payload_leaves = []
        for i, m in enumerate(mixed_leaves):
            g = _roundtrip(m, t, i)[recv]
            if sel_send is not None:
                g = jnp.where(sel_send[i], g, jnp.zeros_like(g))
            payload_leaves.append(g)
        payload = jax.tree.unflatten(treedef, payload_leaves)
        new_ring = {
            "slots": tuple(slots[1:]) + (payload,),
            "valid": jnp.concatenate(
                [valid[:, 1:], ok.astype(jnp.float32)[:, None]], axis=1),
            "t": t + 1,
        }
        losses, grads = grad_fn(mixed, batch)
        new_params, opt_state = optimizer.update(mixed, grads, opt_state)
        metrics = {
            "loss": losses.mean(),
            "replica_variance": replica_variance(mixed),
        }
        return opt_state, new_params, new_ring, metrics

    return step
