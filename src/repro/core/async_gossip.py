"""Bounded-delay asynchronous gossip runtime: the staleness-k inbox ring
(GossipGraD §4.2/§5).

The paper's premise is that a gossip exchange is *not expected to be
reliable or prompt*: a partner model that arrives late is still a valid
diffusion step, and one that never arrives can simply be skipped without
breaking the mixing analysis. PR 2 implemented the staleness-1 special case
(one inbox slot, every exchange lands exactly one step late). This module
generalizes that into a **bounded-delay runtime** where staleness is a
parameter:

    ring entering step t (k = staleness):
        slots[0..k-1]   payloads dispatched at steps t-k .. t-1
                        (slots[0] is the oldest — consumed this step)
        valid[:, 0..k-1] per-slot landed/valid mask (1.0 / 0.0)
        t               dispatch counter (drives the drop injection)

    one step:
        1. a_eff  = alpha * valid[:, 0]                  (masked alpha)
           mixed  = (1 - a_eff) * params + a_eff * slots[0]
        2. payload = ppermute(mixed, schedule row t)      (dispatch, async)
           ok      = exchange_ok(t, rank)                 (drop injection)
        3. ring'   = slots[1:] + [payload],  valid' = [valid[:,1:], ok],
           t' = t + 1

    — i.e. the exchange dispatched at step t has k full steps of compute to
    cross the wire before anything waits on it, and the FIFO queue
    discipline keeps the ring position static inside jit (no dynamic
    indexing: consuming is always ``slots[0]``, appending is structural).

**Skip-on-timeout**: a dropped or late exchange is expressed as mixing with
alpha = 0 — the consumed slot's validity scales alpha, so the mixing-matrix
row for a skipped rank degenerates to the identity row. Every row still
sums to 1 (row-stochastic), so a constant consensus state is a fixed point
under any drop pattern; with no drops the matrix is the same doubly
stochastic (1-a)I + aP as the synchronous mix and the replica mean is
preserved exactly. On a real mesh the validity would be set by the
receive-timeout; on this container drops are *injected* by a deterministic
integer hash of (dispatch step, receiver rank) — ``exchange_ok`` — shared
bit-for-bit by the simulator oracle and the shard_map engines.

Staleness-1 with zero drops reproduces PR 2/3 exactly: the ring has one
slot, a_eff == alpha after the bootstrap, and every fp32 op sequence is
unchanged (the masked-alpha kernels compute the same arithmetic with alpha
read from a coefficient instead of baked in).

Bootstrap: a fresh run starts with k copies of the params and ``valid = 0``
("nothing received yet"): the first k arrival mixes are skips, and the
exchange dispatched at step 0 is consumed at step k. Checkpoints persist
the ring (slots + mask + t) like any state subtree; a checkpoint written at
one staleness restores into another by mask-padding / truncation
(checkpoint.io).

Like the synchronous engine, two phase-selection modes exist: ``static``
(one compiled step per schedule row — the production shape) and ``dynamic``
(``lax.switch`` over all rows with a traced step index). The oracle is
``core.simulate.gossip_mix_sim_delayed_k``; the shard_map implementations
here must match it bit-exactly (tests/test_async_gossip.py).

The **fused mix+apply engine** (``make_packed_fused_async_update``) keeps
PR 3's single-sweep property: the consumed slot is the mix operand of the
fused update kernel and the masked alpha rides the kernel's coefficient
block, so the skip costs no extra pass either.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.quantize import (WireFormat, payload_spec,
                                    zero_payload_like)

from .buckets import (BucketLayout, PackedParams, check_layout_mesh,
                      packed_param_specs)
from .gossip import (_encode_bucket, _wire_mix_one, fused_opt_state_specs,
                     linear_pairs, packed_fused_local_update, wire_period,
                     wire_subset_of)
from .topology import GossipSchedule

PyTree = Any

__all__ = ["exchange_ok", "init_inbox_ring", "inbox_ring_specs",
           "init_wire_inbox_ring", "wire_inbox_ring_specs",
           "make_async_gossip_mix", "make_packed_async_gossip_mix",
           "make_packed_fused_async_update"]


# ------------------------------------------------------- drop-mask injection

def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix32 finalizer over uint32 (wrapping arithmetic)."""
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def exchange_ok(t, rank, seed: int = 0, rate: float = 0.0) -> jnp.ndarray:
    """Emulated-wire drop injection: 1.0 when the exchange dispatched at
    step ``t`` lands at receiver ``rank`` within its staleness-k deadline,
    0.0 when it times out and must be skipped.

    A deterministic integer hash (no jax.random machinery), so the
    simulator oracle, the shard_map engines, and resumed runs agree
    bit-for-bit — vectorized over ``rank`` or evaluated per device, the
    uint32 lanes are independent and identical. ``rate`` is the marginal
    drop probability; 0 disables injection (all-ones mask).
    """
    rank = jnp.asarray(rank)
    if rate <= 0.0:
        return jnp.ones(rank.shape, jnp.float32)
    x = (jnp.asarray(t, jnp.uint32) * jnp.uint32(0x9E3779B9)
         ^ rank.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
         ^ jnp.uint32(seed & 0xFFFFFFFF))
    thresh = jnp.uint32(min(int(rate * (1 << 32)), (1 << 32) - 1))
    return (_mix32(x) >= thresh).astype(jnp.float32)


# ----------------------------------------------------------- ring structure

def init_inbox_ring(params: PyTree, staleness: int, dp: int) -> Dict:
    """Fresh-run bootstrap of the staleness-k inbox ring: k slot copies of
    the params (copies, not aliases — the packed engine donates state
    buffers in place), an all-invalid mask ("nothing received yet", so the
    first k arrival mixes are skips), and dispatch counter 0."""
    if staleness < 1:
        raise ValueError(f"inbox ring needs staleness >= 1, got {staleness}")
    return {
        "slots": tuple(jax.tree.map(jnp.copy, params)
                       for _ in range(int(staleness))),
        "valid": jnp.zeros((max(dp, 1), int(staleness)), jnp.float32),
        "t": jnp.zeros((), jnp.int32),
    }


def inbox_ring_specs(param_specs: PyTree, dp_axes: Sequence[str],
                     staleness: int) -> Dict:
    """PartitionSpec tree matching ``init_inbox_ring``'s structure: every
    slot mirrors the param specs, the (dp, k) validity mask is sharded on
    the replica axis only, the dispatch counter is replicated."""
    dp_axes = tuple(dp_axes)
    front = (dp_axes if len(dp_axes) > 1 else dp_axes[0]) if dp_axes else None
    return {
        "slots": tuple(param_specs for _ in range(int(staleness))),
        "valid": P(front, None),
        "t": P(),
    }


def init_wire_inbox_ring(packed: PackedParams, staleness: int, dp: int,
                         wire: WireFormat) -> Dict:
    """Bootstrap of the staleness-k inbox ring for a COMPRESSED wire: every
    slot is a tuple-over-buckets of all-zero wire payloads (codes + scales
    for int8/fp8; a zero bucket for fp32/bf16) instead of a params copy —
    zero payloads decode to exact zeros and the all-invalid mask means the
    first k arrival mixes consume them only at alpha = 0. Works on global
    (dp, rows, 128) buckets (trainer init / simulator) alike."""
    if staleness < 1:
        raise ValueError(f"inbox ring needs staleness >= 1, got {staleness}")
    slot = tuple(zero_payload_like(b, wire.dtype) for b in packed.buckets)
    return {
        "slots": tuple(jax.tree.map(jnp.copy, slot)
                       for _ in range(int(staleness))),
        "valid": jnp.zeros((max(dp, 1), int(staleness)), jnp.float32),
        "t": jnp.zeros((), jnp.int32),
    }


def wire_inbox_ring_specs(packed_specs: PackedParams, dp_axes: Sequence[str],
                          staleness: int, wire: WireFormat) -> Dict:
    """PartitionSpec tree matching ``init_wire_inbox_ring``: each slot is a
    tuple of per-bucket payload specs — quantized payload codes take the
    bucket's sharding and their per-row scales the same without the lane
    entry (strides are LANE multiples, so the rows divide evenly across
    shard-local layouts)."""
    dp_axes = tuple(dp_axes)
    front = (dp_axes if len(dp_axes) > 1 else dp_axes[0]) if dp_axes else None
    slot = tuple(payload_spec(s, wire.dtype) for s in packed_specs.buckets)
    return {
        "slots": tuple(slot for _ in range(int(staleness))),
        "valid": P(front, None),
        "t": P(),
    }


def _linear_rank(mesh: Mesh, axis_names: Tuple[str, ...]) -> jnp.ndarray:
    """This device's position in the linearized replica space — the same
    row-major linearization ``ppermute`` pairs use over ``axis_names``."""
    idx = jnp.zeros((), jnp.int32)
    for a in axis_names:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _ring_advance(slots, valid, t, payload, ok) -> Dict:
    """FIFO advance of the local ring shard: drop the consumed slot, append
    the fresh dispatch with its landed/dropped flag."""
    ok_col = jnp.broadcast_to(
        jnp.asarray(ok, jnp.float32).reshape(1, 1), (valid.shape[0], 1))
    return {"slots": tuple(slots[1:]) + (payload,),
            "valid": jnp.concatenate([valid[:, 1:], ok_col], axis=1),
            "t": t + 1}


# --------------------------------------------------------- unfused engines

def make_async_gossip_mix(
    mesh: Mesh,
    axis_names: Sequence[str],
    schedule: GossipSchedule,
    param_specs: PyTree,
    *,
    alpha: float = 0.5,
    staleness: int = 1,
    drop_rate: float = 0.0,
    drop_seed: int = 0,
    mode: str = "static",
    mix_impl: Callable | None = None,
) -> Callable[[PyTree, Dict, Any], Tuple[PyTree, Dict]]:
    """Build ``mix(params, ring, phase) -> (mixed, new_ring)``.

    ``params`` leaves carry a leading replica axis over ``axis_names``;
    ``ring`` is an ``init_inbox_ring`` structure whose slots share the
    params' structure and sharding. At phase t the arrival mix consumes the
    oldest slot scaled by its validity (a skipped exchange mixes with
    alpha = 0), and the outgoing ppermute of the mixed params is issued with
    schedule row t; its result is only returned as ring state, so the
    transfer has ``staleness`` full steps of caller-scheduled compute to
    land. ``mix_impl(local, received, alpha)`` swaps in the Pallas bucket
    kernel on the packed path — it receives the masked alpha as a traced
    scalar (the kernels' masked-alpha operand path).
    """
    axis_names = tuple(axis_names)
    dp = int(np.prod([mesh.shape[a] for a in axis_names]))
    if schedule.p != dp:
        raise ValueError(
            f"schedule built for p={schedule.p} but mesh axes {axis_names} "
            f"give dp={dp}")
    if staleness < 1:
        raise ValueError(f"gossip_async needs staleness >= 1, got {staleness}")
    k = int(staleness)
    all_pairs = [linear_pairs(schedule, t) for t in range(schedule.period)]
    ring_specs = inbox_ring_specs(param_specs, axis_names, k)

    def local_async(pairs, params, ring):
        slots, valid, t = ring["slots"], ring["valid"], ring["t"]
        a = alpha * valid[:, 0]                    # masked alpha, (local_dp,)

        def mix_leaf(x, b):
            if mix_impl is not None:
                return mix_impl(x, b, a.reshape(-1)[0])
            w = a.reshape(a.shape + (1,) * (x.ndim - 1))
            return x * (1.0 - w) + b * w

        mixed = jax.tree.map(mix_leaf, params, slots[0])
        payload = jax.tree.map(
            lambda m: jax.lax.ppermute(m, axis_names, pairs), mixed)
        ok = exchange_ok(t, _linear_rank(mesh, axis_names),
                         drop_seed, drop_rate)
        return mixed, _ring_advance(slots, valid, t, payload, ok)

    in_specs = (param_specs, ring_specs)
    out_specs = (param_specs, ring_specs)

    if mode == "static":
        mixers = [
            jax.shard_map(functools.partial(local_async, pairs), mesh=mesh,
                          in_specs=in_specs, out_specs=out_specs,
                          check_vma=False)
            for pairs in all_pairs
        ]

        def mix(params: PyTree, ring: Dict, phase: int):
            return mixers[int(phase) % schedule.period](params, ring)

        return mix

    if mode == "dynamic":
        def body(params: PyTree, ring: Dict, phase: jnp.ndarray):
            branches = [functools.partial(local_async, pairs)
                        for pairs in all_pairs]
            return jax.lax.switch(phase % schedule.period, branches,
                                  params, ring)

        inner = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs + (P(),), out_specs=out_specs,
            check_vma=False)

        def mix(params: PyTree, ring: Dict, phase):
            return inner(params, ring, jnp.asarray(phase, jnp.int32))

        return mix

    raise ValueError(f"unknown gossip mode {mode!r}")


def make_packed_async_gossip_mix(
    mesh: Mesh,
    axis_names: Sequence[str],
    schedule: GossipSchedule,
    layout: BucketLayout,
    *,
    alpha: float = 0.5,
    staleness: int = 1,
    drop_rate: float = 0.0,
    drop_seed: int = 0,
    mode: str = "static",
    mix_impl: Callable | None = None,
    wire: WireFormat | None = None,
) -> Callable[[PyTree, Dict, Any], Tuple[PyTree, Dict]]:
    """Bounded-delay async mix over persistent gossip buckets.

    Both the live params and every ring slot are PackedParams over the same
    layout: the slots are literally the last k steps' wire buffers, kept
    resident. Each step issues one ppermute + one (donatable, in-place,
    masked-alpha) mix per bucket; shard-local layouts (fsdp / TP inside a
    replica) are legal exactly as in the sync packed engine — the bucket
    rows shard over the in-replica axes and the ppermute runs over the
    replica axes only (``check_layout_mesh`` validates the agreement).

    ``wire`` (non-default): the compressed + partition-sampled wire. Ring
    slots then hold tuple-over-buckets WIRE PAYLOADS (``init_wire_inbox_ring``
    / ``wire_inbox_ring_specs``): the mixed bucket is encoded on dispatch
    (stochastic rounding keyed on the ring's absolute dispatch counter ``t``
    — matching the simulator oracle bit-for-bit and resumable across
    checkpoints) and the consumed payload decodes inside the arrival-mix
    sweep; buckets outside the rotating subset ship an all-zero payload and
    are consumed at alpha = 0 (statically passed through untouched). The
    consumption mask at phase ``ph`` is ``selected(ph - k)`` — the slot
    consumed now was dispatched k steps ago.
    """
    check_layout_mesh(layout, mesh)
    axis_names = tuple(axis_names)
    specs = packed_param_specs(layout, axis_names)
    if wire is None or wire.is_default:
        return make_async_gossip_mix(mesh, axis_names, schedule, specs,
                                     alpha=alpha, staleness=staleness,
                                     drop_rate=drop_rate, drop_seed=drop_seed,
                                     mode=mode, mix_impl=mix_impl)
    dp = int(np.prod([mesh.shape[a] for a in axis_names]))
    if schedule.p != dp:
        raise ValueError(
            f"schedule built for p={schedule.p} but mesh axes {axis_names} "
            f"give dp={dp}")
    if staleness < 1:
        raise ValueError(f"gossip_async needs staleness >= 1, got {staleness}")
    k = int(staleness)
    subset = wire_subset_of(wire, layout.num_buckets)
    eff = wire_period(schedule, subset)
    all_pairs = [linear_pairs(schedule, t) for t in range(schedule.period)]
    ring_specs = wire_inbox_ring_specs(specs, axis_names, k, wire)

    def local_async_wire(phase_idx: int, params: PackedParams, ring: Dict):
        pairs = all_pairs[phase_idx % schedule.period]
        nb = layout.num_buckets
        sel_cons = (subset.selected(phase_idx - k) if subset is not None
                    else np.ones(nb, bool))
        sel_send = (subset.selected(phase_idx) if subset is not None
                    else np.ones(nb, bool))
        slots, valid, t = ring["slots"], ring["valid"], ring["t"]
        # each device owns exactly one replica row under the packed-engine
        # sharding restriction, so the masked alpha is one traced scalar
        a_eff = alpha * valid[0, 0]
        mixed_buckets = []
        for i, x in enumerate(params.buckets):
            if sel_cons[i]:
                mixed_buckets.append(
                    _wire_mix_one(x, slots[0][i], a_eff, mix_impl))
            else:
                mixed_buckets.append(x)  # unsent on dispatch: exact skip
        mixed = PackedParams(mixed_buckets, layout)
        rank = _linear_rank(mesh, axis_names)
        payload = []
        for i, m in enumerate(mixed.buckets):
            if sel_send[i]:
                enc = _encode_bucket(layout, mesh, wire, m, t, rank, i)
                payload.append(jax.tree.map(
                    lambda e: jax.lax.ppermute(e, axis_names, pairs), enc))
            else:
                payload.append(zero_payload_like(m, wire.dtype))
        ok = exchange_ok(t, rank, drop_seed, drop_rate)
        return mixed, _ring_advance(slots, valid, t, tuple(payload), ok)

    in_specs = (specs, ring_specs)
    out_specs = (specs, ring_specs)

    if mode == "static":
        mixers = [
            jax.shard_map(functools.partial(local_async_wire, ph), mesh=mesh,
                          in_specs=in_specs, out_specs=out_specs,
                          check_vma=False)
            for ph in range(eff)
        ]

        def mix(params, ring, phase):
            return mixers[int(phase) % eff](params, ring)

        return mix

    if mode == "dynamic":
        def body(params, ring, phase):
            branches = [functools.partial(local_async_wire, ph)
                        for ph in range(eff)]
            return jax.lax.switch(phase % eff, branches, params, ring)

        inner = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs + (P(),), out_specs=out_specs,
            check_vma=False)

        def mix(params, ring, phase):
            return inner(params, ring, jnp.asarray(phase, jnp.int32))

        return mix

    raise ValueError(f"unknown gossip mode {mode!r}")


# ------------------------------------------------------------ fused engine

def make_packed_fused_async_update(
    mesh: Mesh,
    axis_names: Sequence[str],
    schedule: GossipSchedule,
    layout: BucketLayout,
    optimizer,
    *,
    alpha: float = 0.5,
    staleness: int = 1,
    drop_rate: float = 0.0,
    drop_seed: int = 0,
    mode: str = "static",
    impl: str | None = None,
    wire: WireFormat | None = None,
) -> Callable:
    """Fused mix+apply engine for the staleness-k inbox ring: build
    ``update(params, grads, ring, opt_state, phase) -> (params',
    opt_state', new_ring)``.

    The consumed ring slot is the mix operand of the single-sweep fused
    kernel (kernels/fused_update.py) and the slot's validity scales alpha
    through the kernel's masked-alpha coefficient — a skipped exchange
    degenerates to the pure local update inside the same sweep, no second
    pass.  The outgoing exchange ``ppermute(params)`` (schedule row
    ``phase``) is dispatched at the TOP of the program — it depends only on
    the incoming params, so XLA hoists the whole forward/backward between
    collective-permute start/done — and its result is returned solely as
    the newest ring slot, giving the wire ``staleness`` full steps to land.
    As in PR 3, the per-step ALGEBRA differs from the unfused inbox
    protocol: the wire carries the raw incoming params (the unfused path
    transmits the post-arrival-mix params) and gradients are evaluated at
    the pre-mix params — the GoSGD-style combined update.  The mixing
    matrix per step is unchanged ((1-a_eff)I + a_eff P, row-stochastic;
    doubly stochastic when nothing is dropped), so mean preservation and
    the diffusion argument carry over.  Fresh runs bootstrap with an
    all-invalid ring (``init_inbox_ring``), making the first k arrival
    mixes identity.

    ``wire`` (non-default): ring slots hold tuple-over-buckets wire
    payloads (``init_wire_inbox_ring``), the outbox encodes the RAW
    pre-update buckets (noise keyed on the ring's dispatch counter ``t``),
    and the consumed payload's codes + scales feed the fused kernel's
    partner/scale streams — the decode still rides the single sweep.
    Partition-sampled buckets outside the dispatch subset ship zero
    payloads; outside the consumption subset (``selected(phase - k)``)
    the kernel runs the pure local update (partner = None, alpha = 0).
    """
    axis_names = tuple(axis_names)
    dp = int(np.prod([mesh.shape[a] for a in axis_names]))
    if schedule.p != dp:
        raise ValueError(
            f"schedule built for p={schedule.p} but mesh axes {axis_names} "
            f"give dp={dp}")
    if staleness < 1:
        raise ValueError(f"gossip_async needs staleness >= 1, got {staleness}")
    k = int(staleness)
    check_layout_mesh(layout, mesh)
    specs = packed_param_specs(layout, axis_names)
    wired = wire is not None and not wire.is_default
    subset = wire_subset_of(wire, layout.num_buckets) if wired else None
    eff = wire_period(schedule, subset) if wired else schedule.period
    ring_specs = (wire_inbox_ring_specs(specs, axis_names, k, wire)
                  if wired else inbox_ring_specs(specs, axis_names, k))
    local = packed_fused_local_update(layout, optimizer, alpha=alpha,
                                      impl=impl)
    all_pairs = [linear_pairs(schedule, t) for t in range(schedule.period)]

    def local_async_wire(phase_idx, params, grads, ring, opt_state):
        pairs = all_pairs[phase_idx % schedule.period]
        nb = layout.num_buckets
        sel_cons = (subset.selected(phase_idx - k) if subset is not None
                    else np.ones(nb, bool))
        sel_send = (subset.selected(phase_idx) if subset is not None
                    else np.ones(nb, bool))
        slots, valid, t = ring["slots"], ring["valid"], ring["t"]
        rank = _linear_rank(mesh, axis_names)
        # dispatch first: the outbox encodes the RAW incoming params and is
        # consumed only as returned ring state — the wire overlaps the whole
        # fwd/bwd plus the next staleness-1 steps entirely
        outbox = []
        for i, b in enumerate(params.buckets):
            if sel_send[i]:
                enc = _encode_bucket(layout, mesh, wire, b, t, rank, i)
                with jax.named_scope("exchange"):
                    outbox.append(jax.tree.map(
                        lambda e: jax.lax.ppermute(e, axis_names, pairs), enc))
            else:
                outbox.append(zero_payload_like(b, wire.dtype))
        # each device owns exactly one replica row under the packed-engine
        # sharding restriction, so the masked alpha is one traced scalar
        a_eff = alpha * valid[0, 0]
        partners = [slots[0][i] if sel_cons[i] else None for i in range(nb)]
        alphas = [a_eff if sel_cons[i] else 0.0 for i in range(nb)]
        new_params, new_state = local(params, grads, opt_state, partners,
                                      alpha_eff=alphas)
        ok = exchange_ok(t, rank, drop_seed, drop_rate)
        return new_params, new_state, _ring_advance(slots, valid, t,
                                                    tuple(outbox), ok)

    def local_async(pairs, params, grads, ring, opt_state):
        # dispatch first: the outbox depends only on the incoming params
        # and is consumed only as returned ring state — the wire overlaps
        # everything scheduled before this call (the whole fwd/bwd) plus
        # the next staleness-1 steps entirely
        slots, valid, t = ring["slots"], ring["valid"], ring["t"]
        with jax.named_scope("exchange"):
            outbox = PackedParams(
                [jax.lax.ppermute(b, axis_names, pairs)
                 for b in params.buckets], layout)
        # each device owns exactly one replica row under the packed-engine
        # sharding restriction, so the masked alpha is one traced scalar
        a_eff = alpha * valid[0, 0]
        new_params, new_state = local(params, grads, opt_state, slots[0],
                                      alpha_eff=a_eff)
        ok = exchange_ok(t, _linear_rank(mesh, axis_names),
                         drop_seed, drop_rate)
        return new_params, new_state, _ring_advance(slots, valid, t,
                                                    outbox, ok)

    def opt_specs_of(opt_state):
        return fused_opt_state_specs(opt_state, specs)

    if mode == "static":
        def update(params, grads, ring, opt_state, phase):
            opt_specs = opt_specs_of(opt_state)
            if wired:
                body = functools.partial(local_async_wire, int(phase) % eff)
            else:
                body = functools.partial(
                    local_async, all_pairs[int(phase) % schedule.period])
            fn = jax.shard_map(
                body, mesh=mesh,
                in_specs=(specs, specs, ring_specs, opt_specs),
                out_specs=(specs, opt_specs, ring_specs), check_vma=False)
            return fn(params, grads, ring, opt_state)

        return update

    if mode == "dynamic":
        def update(params, grads, ring, opt_state, phase):
            opt_specs = opt_specs_of(opt_state)

            def body(params, grads, ring, opt_state, ph):
                if wired:
                    branches = [functools.partial(local_async_wire, i)
                                for i in range(eff)]
                else:
                    branches = [functools.partial(local_async, pairs)
                                for pairs in all_pairs]
                return jax.lax.switch(ph % eff, branches,
                                      params, grads, ring, opt_state)

            inner = jax.shard_map(
                body, mesh=mesh,
                in_specs=(specs, specs, ring_specs, opt_specs, P()),
                out_specs=(specs, opt_specs, ring_specs), check_vma=False)
            return inner(params, grads, ring, opt_state,
                         jnp.asarray(phase, jnp.int32))

        return update

    raise ValueError(f"unknown gossip mode {mode!r}")
