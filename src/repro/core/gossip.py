"""Distributed gossip mixing on a TPU mesh (GossipGraD §4–5, TPU-native).

The paper's per-step exchange — MPI_Isend to ``(i + 2^k) % p`` / MPI_Irecv
from ``(i - 2^k) % p`` followed by ``w <- (w + w_recv)/2`` — maps exactly onto
one ``jax.lax.ppermute`` (XLA ``collective-permute``) over the data-parallel
mesh axes inside ``shard_map``: every device sends its *local shard* of the
replica-axis-sharded parameter tree to its partner and averages. Communication
volume per chip per step is ``bytes(local shard)`` — **O(1) in p**, the
paper's headline property — versus ``~2·bytes(shard)·(p-1)/p`` with ``log p``
latency steps for the all-reduce baseline.

Asynchronicity (§5): the paper posts per-layer non-blocking sends and drives
progress with MPI_TestAll. On TPU, XLA emits ``collective-permute-start/done``
pairs and hoists compute between them natively, so the *structural* analogue
is to issue one ppermute per parameter leaf ("layer-wise", the default) so the
scheduler can overlap each with surrounding compute.

The production path is the **bucketed engine** (``make_packed_gossip_mix``):
parameters live in a handful of persistent LANE-aligned, dtype-homogeneous
buckets (core.buckets) packed once at init; each mix step is one ppermute +
one in-place Pallas mix per bucket — the per-leaf path's overlap surface at
O(buckets) launch cost, with zero per-step packing, zero casts, and native
bf16 wire format.

On top of it sits the **fused mix+apply engine**
(``make_packed_fused_update``): the gossip mix and the optimizer update are
one single-sweep kernel per bucket (kernels/fused_update.py), so a step
makes ONE fused read pass and ONE fused write pass over the parameter state
instead of the mix pass plus 2-3 optimizer passes.  The fused step dispatches
``ppermute(params)`` — the partner's pre-update params — at the top of the
step and consumes the result only in the end-of-step fused update, so the
wire overlaps the whole forward/backward (the GoSGD-style combined update:
the partner contribution trails the local one by exactly the one update the
async inbox protocol also misses).

Two phase-selection modes:

* ``static`` (default): the gossip step's position in the schedule is a
  static Python int baked into the compiled step (the launcher keeps
  ``schedule.period`` compiled variants — the production-realistic analogue of
  per-step MPI tags). This is what the multi-pod dry-run lowers.
* ``dynamic``: ``lax.switch`` over all ``period`` permutations with a traced
  step index — one compiled step total; validated on CPU.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.quantize import (WireFormat, decode_wire, encode_wire,
                                    wire_itemsize, wire_key)

from .buckets import (BucketLayout, PackedParams, check_layout_mesh,
                      packed_param_specs)
from .topology import (BucketSubsetSchedule, GossipSchedule,
                       build_subset_schedule)

PyTree = Any

__all__ = [
    "linear_pairs",
    "make_gossip_mix",
    "make_packed_gossip_mix",
    "make_packed_fused_update",
    "gossip_bytes_per_step",
    "wire_period",
    "wire_subset_of",
    "wire_bytes_per_step",
]


# ----------------------------------------------------- compressed-wire plumbing

def wire_subset_of(wire: WireFormat | None,
                   num_buckets: int) -> BucketSubsetSchedule | None:
    """The rotating bucket-subset schedule implied by a wire format (None
    for full participation — including ``wire=None``, the PR-1..5 path)."""
    if wire is None:
        return None
    return build_subset_schedule(num_buckets, wire.subset)


def wire_period(schedule: GossipSchedule | None,
                subset: BucketSubsetSchedule | None) -> int:
    """Effective phase period of a (partner schedule, bucket subset) pair:
    lcm of the two rotations — the protocol's ``period`` (the Trainer mods
    the step by it BEFORE the engines see a phase, so the subset rotation
    must divide it)."""
    per = schedule.period if schedule is not None else 1
    if subset is None:
        return per
    return per * subset.period // math.gcd(per, subset.period)


def _axis_rank(mesh: Mesh, axis_names: Tuple[str, ...]):
    """This device's position in the row-major linearization of
    ``axis_names`` (traced; must run inside shard_map)."""
    idx = jnp.zeros((), jnp.int32)
    for a in axis_names:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _wire_base_index(layout: BucketLayout, mesh: Mesh, bucket_index: int):
    """GLOBAL element offset of this device's shard of bucket
    ``bucket_index`` — keys the stochastic-rounding noise by global element
    position, so shard-local (fsdp) engines and the full-bucket simulator
    oracle draw identical noise (kernels.quantize discipline)."""
    if getattr(layout, "num_shards", 1) <= 1:
        return 0
    srank = _axis_rank(mesh, tuple(layout.shard_axes))
    return srank * layout.strides[bucket_index]


def _encode_bucket(layout: BucketLayout, mesh: Mesh, wire: WireFormat,
                   bucket: jnp.ndarray, t, rank, bucket_index: int):
    """Dispatch-side wire encode of one local bucket shard (plain jnp —
    shared verbatim with the simulator oracle, hence bit-exact)."""
    keys = wire_key(t, rank, bucket_index, wire.seed)
    return encode_wire(bucket, wire.dtype, keys=keys,
                       base_index=_wire_base_index(layout, mesh, bucket_index))


def _wire_mix_one(x: jnp.ndarray, payload, alpha, mix_impl: Callable | None):
    """Arrival mix of one bucket against its wire payload. ``mix_impl``
    (kernels.gossip_mix_wire_bucket on the packed path) folds the quantized
    decode into the kernel sweep; the jnp fallback runs the identical fp32
    op order (decode, then (1-a)*x + a*b, cast back)."""
    if mix_impl is not None:
        return mix_impl(x, payload, alpha)
    b = decode_wire(payload)
    return (x.astype(jnp.float32) * (1.0 - alpha)
            + b.astype(jnp.float32) * alpha).astype(x.dtype)


def linear_pairs(schedule: GossipSchedule, step: int) -> Tuple[Tuple[int, int], ...]:
    """(src, dst) pairs over the linearized data-parallel axes at ``step``."""
    return tuple(schedule.ppermute_pairs(step))


def _mix_leaf(x: jnp.ndarray, axis_names: Tuple[str, ...],
              pairs: Tuple[Tuple[int, int], ...], alpha: float,
              mix_impl: Callable | None) -> jnp.ndarray:
    recv = jax.lax.ppermute(x, axis_names, pairs)
    if mix_impl is not None:  # e.g. the Pallas gossip_mix kernel
        return mix_impl(x, recv, alpha)
    return x * (1.0 - alpha) + recv * alpha


def make_gossip_mix(
    mesh: Mesh,
    axis_names: Sequence[str],
    schedule: GossipSchedule,
    param_specs: PyTree,
    *,
    alpha: float = 0.5,
    mode: str = "static",
    mix_impl: Callable | None = None,
) -> Callable[[PyTree, Any], PyTree]:
    """Build ``mix(params, phase) -> params``.

    ``params`` leaves carry a leading replica axis sharded over ``axis_names``
    (their PartitionSpecs given by ``param_specs``). ``phase`` is the gossip
    step index: a Python int in ``static`` mode, a traced int32 in ``dynamic``
    mode. ``alpha=0.5`` is the paper's pairwise average; other alphas give the
    general symmetric-gossip mix (beyond-paper knob).
    """
    axis_names = tuple(axis_names)
    dp = int(np.prod([mesh.shape[a] for a in axis_names]))
    if schedule.p != dp:
        raise ValueError(
            f"schedule built for p={schedule.p} but mesh axes {axis_names} "
            f"give dp={dp}")

    def local_mix(pairs: Tuple[Tuple[int, int], ...], params: PyTree) -> PyTree:
        return jax.tree.map(
            lambda x: _mix_leaf(x, axis_names, pairs, alpha, mix_impl), params)

    return _phase_dispatch(mesh, schedule, param_specs, local_mix, mode)


def _phase_dispatch(mesh: Mesh, schedule: GossipSchedule, param_specs: PyTree,
                    local_mix: Callable, mode: str) -> Callable:
    """Wrap a per-device ``local_mix(pairs, params)`` into ``mix(params,
    phase)`` under shard_map, with static or dynamic phase selection."""
    all_pairs = [linear_pairs(schedule, t) for t in range(schedule.period)]

    def shmapped(fn):
        return jax.shard_map(
            fn, mesh=mesh, in_specs=(param_specs,), out_specs=param_specs,
            check_vma=False)

    if mode == "static":
        mixers = [shmapped(functools.partial(local_mix, pairs))
                  for pairs in all_pairs]

        def mix(params: PyTree, phase: int) -> PyTree:
            return mixers[int(phase) % schedule.period](params)

        return mix

    if mode == "dynamic":
        def body(params: PyTree, phase: jnp.ndarray) -> PyTree:
            branches = [functools.partial(local_mix, pairs)
                        for pairs in all_pairs]
            return jax.lax.switch(phase % schedule.period, branches, params)

        inner = jax.shard_map(
            body, mesh=mesh, in_specs=(param_specs, P()), out_specs=param_specs,
            check_vma=False)

        def mix(params: PyTree, phase) -> PyTree:
            return inner(params, jnp.asarray(phase, jnp.int32))

        return mix

    raise ValueError(f"unknown gossip mode {mode!r}")


def make_packed_gossip_mix(
    mesh: Mesh,
    axis_names: Sequence[str],
    schedule: GossipSchedule,
    layout: BucketLayout,
    *,
    alpha: float = 0.5,
    mode: str = "static",
    mix_impl: Callable | None = None,
    wire: WireFormat | None = None,
) -> Callable[[PyTree, Any], PyTree]:
    """Build ``mix(packed, phase) -> packed`` over persistent gossip buckets.

    ``packed`` is a core.buckets.PackedParams whose buckets carry a leading
    replica axis sharded over ``axis_names``. Each step issues exactly one
    ppermute + one mix per bucket — no per-step concatenation, no casts
    (buckets are dtype-homogeneous), and the mix can run in place
    (``mix_impl`` defaults to plain jnp; pass kernels.gossip_mix_bucket for
    the donation-friendly Pallas path).

    Layouts sharded INSIDE a replica (fsdp / tensor parallelism) are legal
    when the layout is shard-local (built with the distribution's in-replica
    axes — core.buckets): the bucket rows then shard over those axes so
    each device's local block is its own shard bytes, and the ppermute still
    runs over the replica axes only. ``check_layout_mesh`` validates the
    layout/mesh agreement (the shard-aware successor of the old "only
    sharded on the replica axis" guard).

    ``wire`` (non-default): the compressed + partition-sampled wire. Each
    SELECTED bucket (rotating subset, ``core.topology.build_subset_schedule``)
    is encoded on the dispatch side (int8 stochastic / fp8 / bf16 — see
    kernels.quantize), the codes+scales are ppermuted, and the decode folds
    into the arrival-mix sweep; UNSENT buckets issue no collective and pass
    through untouched (bit-exact skip). Phase arithmetic runs modulo
    ``wire_period(schedule, subset)``; the sync wire keys its
    stochastic-rounding noise on that phase, so noise is periodic in the
    effective period (documented contract — the async engines key on the
    absolute dispatch counter instead).
    """
    check_layout_mesh(layout, mesh)
    axis_names = tuple(axis_names)
    specs = packed_param_specs(layout, axis_names)
    if wire is None or wire.is_default:
        return make_gossip_mix(mesh, axis_names, schedule, specs, alpha=alpha,
                               mode=mode, mix_impl=mix_impl)
    dp = int(np.prod([mesh.shape[a] for a in axis_names]))
    if schedule.p != dp:
        raise ValueError(
            f"schedule built for p={schedule.p} but mesh axes {axis_names} "
            f"give dp={dp}")
    subset = wire_subset_of(wire, layout.num_buckets)
    eff = wire_period(schedule, subset)
    all_pairs = [linear_pairs(schedule, t) for t in range(schedule.period)]

    def local_mix(phase_idx: int, params: PackedParams) -> PackedParams:
        pairs = all_pairs[phase_idx % schedule.period]
        sel = (subset.selected(phase_idx) if subset is not None
               else np.ones(layout.num_buckets, bool))
        rank = _axis_rank(mesh, axis_names)
        new = []
        for i, x in enumerate(params.buckets):
            if not sel[i]:
                new.append(x)  # unsent: no collective, untouched bits
                continue
            enc = _encode_bucket(layout, mesh, wire, x, phase_idx, rank, i)
            recv = jax.tree.map(
                lambda e: jax.lax.ppermute(e, axis_names, pairs), enc)
            new.append(_wire_mix_one(x, recv, alpha, mix_impl))
        return PackedParams(new, layout)

    if mode == "static":
        mixers = [
            jax.shard_map(functools.partial(local_mix, ph), mesh=mesh,
                          in_specs=(specs,), out_specs=specs, check_vma=False)
            for ph in range(eff)
        ]

        def mix(params, phase):
            return mixers[int(phase) % eff](params)

        return mix

    if mode == "dynamic":
        def body(params, phase):
            branches = [functools.partial(local_mix, ph) for ph in range(eff)]
            return jax.lax.switch(phase % eff, branches, params)

        inner = jax.shard_map(
            body, mesh=mesh, in_specs=(specs, P()), out_specs=specs,
            check_vma=False)

        def mix(params, phase):
            return inner(params, jnp.asarray(phase, jnp.int32))

        return mix

    raise ValueError(f"unknown gossip mode {mode!r}")


# --------------------------------------------------------------------------
# Fused mix+apply engine: one single-sweep kernel per bucket per step.
# --------------------------------------------------------------------------

def packed_fused_local_update(layout: BucketLayout, optimizer, *,
                              alpha: float, impl: str | None = None):
    """Per-device body of the fused engine: ``body(params, grads, opt_state,
    partner, alpha_eff=None) -> (params', opt_state')`` over local
    PackedParams shards.

    ``partner`` is the mix operand (the landed ppermute result — sync recv
    or async ring slot), or None for the pure local update (alpha treated as
    0).  It may also be a LIST of per-bucket operands (array, quantized
    ``{"q","s"}`` wire payload, or None for an unsent bucket — the
    partition-sampled wire), in which case ``alpha_eff`` may be a matching
    list of per-bucket alphas (0.0 for unsent buckets).  ``alpha_eff``
    overrides the closure alpha per call — the bounded-delay engine passes
    the masked alpha (the static alpha scaled by the consumed slot's
    validity) as a traced scalar, which the kernels consume through their
    masked-alpha coefficient path.  One ``optimizer.fused_update`` call — a
    single read+write sweep — per bucket; the step counter advances exactly
    like the tree-level update.  Shared by the sync engine below and the
    async engine in async_gossip.py.
    """
    if optimizer.fused_update is None:
        raise ValueError(
            "optimizer has no fused_update backend; use sgd/adamw/lars or "
            "the unfused mix-then-apply path")
    moment_keys = tuple(optimizer.fused_moments)

    def body(params, grads, opt_state, partner, alpha_eff=None):
        per_bucket = isinstance(partner, (list, tuple))
        if alpha_eff is None:
            alpha_eff = alpha if partner is not None else 0.0
        step = opt_state["step"]
        new_buckets = []
        new_moms = [[] for _ in moment_keys]
        for i in range(layout.num_buckets):
            moms = tuple(
                opt_state[k].buckets[i] if opt_state[k] is not None else None
                for k in moment_keys)
            if per_bucket:
                mix_operand = partner[i]
                a_i = (alpha_eff[i]
                       if isinstance(alpha_eff, (list, tuple)) else alpha_eff)
            else:
                mix_operand = (partner.buckets[i]
                               if partner is not None else None)
                a_i = alpha_eff
            p2, m2 = optimizer.fused_update(
                i, params.buckets[i], grads.buckets[i], mix_operand, moms,
                step=step, alpha=a_i, layout=layout, impl=impl)
            new_buckets.append(p2)
            for j, mv in enumerate(m2):
                new_moms[j].append(mv)
        new_state = {"step": step + 1}
        for j, k in enumerate(moment_keys):
            new_state[k] = (PackedParams(new_moms[j], layout)
                            if opt_state[k] is not None else None)
        return PackedParams(new_buckets, layout), new_state

    return body


def fused_opt_state_specs(opt_state, specs: PyTree) -> dict:
    """PartitionSpec tree for a fused-engine optimizer state: the step
    counter is replicated, every moment tree mirrors the bucket specs."""
    from jax.sharding import PartitionSpec as P
    return {k: (P() if k == "step" else None if v is None else specs)
            for k, v in opt_state.items()}


def make_packed_fused_update(
    mesh: Mesh,
    axis_names: Sequence[str],
    schedule: GossipSchedule | None,
    layout: BucketLayout,
    optimizer,
    *,
    alpha: float = 0.5,
    mode: str = "static",
    impl: str | None = None,
    wire: WireFormat | None = None,
) -> Callable:
    """Build ``update(params, grads, opt_state, phase) -> (params',
    opt_state')`` — the synchronous fused mix+apply engine.

    With a ``schedule`` (dp > 1 gossip): each step dispatches one
    ``ppermute(params)`` per bucket at the TOP of the program (the partner's
    pre-update params — nothing below depends on it until the fused update,
    so XLA hoists the whole forward/backward between collective-permute
    start/done) and consumes the received buckets as the mix operand of the
    single-sweep fused kernel.  The partner contribution therefore trails
    the local gradient step by exactly one update — the same GoSGD-style
    staleness the paper's §5 asynchrony embraces; the mixing matrix per step
    is unchanged ((1-a)I + aP, doubly stochastic).

    With ``schedule=None`` (dp == 1, or non-gossip protocols): no collective
    is issued and the same kernel runs with alpha = 0 — one compiled step
    body shape for every phase of every protocol.

    ``wire`` (non-default): the compressed + partition-sampled wire — each
    SELECTED bucket's raw pre-update params are encoded on dispatch
    (kernels.quantize), the codes+scales ppermuted, and the decode folds
    into the fused kernel sweep (the scale column stream); UNSENT buckets
    issue no collective and take the pure local update (per-bucket
    alpha = 0 through the masked-alpha path). Phases run modulo
    ``wire_period(schedule, subset)``.
    """
    axis_names = tuple(axis_names)
    check_layout_mesh(layout, mesh)
    specs = packed_param_specs(layout, axis_names)
    local = packed_fused_local_update(layout, optimizer,
                                      alpha=alpha if schedule is not None
                                      else 0.0, impl=impl)

    def shmapped(fn, opt_specs):
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=(specs, specs, opt_specs),
            out_specs=(specs, opt_specs), check_vma=False)

    def opt_specs_of(opt_state):
        return fused_opt_state_specs(opt_state, specs)

    if schedule is None:
        def update(params, grads, opt_state, phase=None):
            fn = shmapped(lambda p, g, s: local(p, g, s, None),
                          opt_specs_of(opt_state))
            return fn(params, grads, opt_state)

        return update

    dp = int(np.prod([mesh.shape[a] for a in axis_names]))
    if schedule.p != dp:
        raise ValueError(
            f"schedule built for p={schedule.p} but mesh axes {axis_names} "
            f"give dp={dp}")
    all_pairs = [linear_pairs(schedule, t) for t in range(schedule.period)]
    wired = wire is not None and not wire.is_default
    subset = wire_subset_of(wire, layout.num_buckets) if wired else None
    eff = wire_period(schedule, subset)

    def local_sync(pairs, params, grads, opt_state):
        # dispatch first: the recv depends only on the incoming params, so
        # the wire runs under everything the caller scheduled before us
        # (the whole fwd/bwd of the train step)
        with jax.named_scope("exchange"):
            recv = PackedParams(
                [jax.lax.ppermute(b, axis_names, pairs)
                 for b in params.buckets], layout)
        return local(params, grads, opt_state, recv)

    def local_sync_wire(phase_idx, params, grads, opt_state):
        pairs = all_pairs[phase_idx % schedule.period]
        sel = (subset.selected(phase_idx) if subset is not None
               else np.ones(layout.num_buckets, bool))
        rank = _axis_rank(mesh, axis_names)
        partners, alphas = [], []
        for i, b in enumerate(params.buckets):
            if not sel[i]:
                partners.append(None)
                alphas.append(0.0)
                continue
            enc = _encode_bucket(layout, mesh, wire, b, phase_idx, rank, i)
            with jax.named_scope("exchange"):
                partners.append(jax.tree.map(
                    lambda e: jax.lax.ppermute(e, axis_names, pairs), enc))
            alphas.append(alpha)
        return local(params, grads, opt_state, partners, alpha_eff=alphas)

    if mode == "static":
        if wired:
            def update(params, grads, opt_state, phase):
                fn = shmapped(
                    functools.partial(local_sync_wire, int(phase) % eff),
                    opt_specs_of(opt_state))
                return fn(params, grads, opt_state)

            return update

        def update(params, grads, opt_state, phase):
            pairs = all_pairs[int(phase) % schedule.period]
            fn = shmapped(functools.partial(local_sync, pairs),
                          opt_specs_of(opt_state))
            return fn(params, grads, opt_state)

        return update

    if mode == "dynamic":
        def update(params, grads, opt_state, phase):
            opt_specs = opt_specs_of(opt_state)

            def body(params, grads, opt_state, ph):
                if wired:
                    branches = [functools.partial(local_sync_wire, p_)
                                for p_ in range(eff)]
                    return jax.lax.switch(ph % eff, branches,
                                          params, grads, opt_state)
                branches = [functools.partial(local_sync, pairs)
                            for pairs in all_pairs]
                return jax.lax.switch(ph % schedule.period, branches,
                                      params, grads, opt_state)

            inner = jax.shard_map(
                body, mesh=mesh,
                in_specs=(specs, specs, opt_specs, P()),
                out_specs=(specs, opt_specs), check_vma=False)
            return inner(params, grads, opt_state,
                         jnp.asarray(phase, jnp.int32))

        return update

    raise ValueError(f"unknown gossip mode {mode!r}")


def gossip_bytes_per_step(replica_bytes: int, dp: int, model_shards: int = 1) -> dict:
    """Analytic per-step communication volume (paper Table 1 economics).

    ``replica_bytes`` is the byte size of ONE model replica; each replica is
    sharded ``model_shards``-way, so a chip's local shard is
    ``replica_bytes / model_shards``. Gossip sends exactly that local shard to
    one partner — independent of dp (the paper's O(1)). Ring all-reduce moves
    ``2·shard·(dp-1)/dp`` per chip with ``~log2(dp)`` latency steps.
    """
    shard = replica_bytes / max(model_shards, 1)
    return {
        "replica_bytes": replica_bytes,
        "gossip_bytes_per_chip": shard if dp > 1 else 0.0,
        "allreduce_bytes_per_chip": 2.0 * shard * (dp - 1) / dp if dp > 1 else 0.0,
        "allreduce_latency_steps": int(np.ceil(np.log2(max(dp, 2)))),
        "gossip_latency_steps": 1,
    }


def wire_bytes_per_step(layout: BucketLayout, wire: WireFormat | None = None
                        ) -> dict:
    """Exact per-chip wire bytes of ONE packed gossip exchange under a wire
    format (the compressed-wire headline accounting).

    ``code_bytes`` counts the ppermuted payload codes only; per-tile fp32
    scales are reported separately (``scale_bytes``) — they ride the
    coefficient block like the per-bucket scalars the fused kernels already
    ship, so the headline compression ratio is exact (int8 = 4x, int8 +
    50% sampling = 8x vs an fp32 bucket wire). ``subset_avg`` averages the
    rotating bucket subset over one full rotation period (every bucket is
    sent ``n_send``-out-of-``num_buckets`` of the time)."""
    wire = wire or WireFormat()
    subset = wire_subset_of(wire, layout.num_buckets)
    # per-chip: each device ppermutes its own stride-element block per bucket
    sizes = [int(s) for s in layout.strides]
    raw, code, scale = 0.0, 0.0, 0.0
    frac = 1.0 if subset is None else subset.fraction
    for i, n in enumerate(sizes):
        dt = layout.bucket_dtypes[i]
        raw += n * int(np.dtype(dt).itemsize)
        code += n * wire_itemsize(wire.dtype, dt) * frac
        if wire.quantized:
            scale += (n // 128) * 4 * frac
    return {
        "raw_bytes": raw,
        "code_bytes": code,
        "scale_bytes": scale,
        "total_bytes": code + scale,
        "reduction_codes": raw / code if code else float("inf"),
        "reduction_total": raw / (code + scale) if code + scale else float("inf"),
        "subset_fraction": frac,
        "wire_dtype": wire.dtype,
    }
