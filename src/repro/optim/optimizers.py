"""Optimizers (pure pytree transforms; states mirror param layout, so they
inherit the replica axis + sharding of the parameters they track).

The paper trains with SGD + momentum (Caffe defaults); AdamW is provided for
the LLM-family configs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from .schedules import Schedule, constant

PyTree = Any

__all__ = ["Optimizer", "sgd", "adamw", "lars"]


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[PyTree], PyTree]
    update: Callable[[PyTree, PyTree, PyTree], Tuple[PyTree, PyTree]]
    # True when update is purely elementwise per leaf — such optimizers are
    # transparent to the bucketed gossip engine (core.buckets), which fuses
    # many layers into one flat leaf. Norm-based per-leaf updates (lars) set
    # False.
    elementwise: bool = True
    # Non-elementwise optimizers that nevertheless handle PackedParams
    # states correctly — by reading per-leaf norms through the
    # ``PackedParams.unpack()`` view — set True to run under the bucketed
    # gossip engine anyway.
    packed_aware: bool = False
    # --- fused mix+apply backend (kernels/fused_update.py) -----------------
    # State keys (beyond "step") holding the per-param moment buffers, in
    # the order ``fused_update`` takes and returns them.
    fused_moments: Tuple[str, ...] = ()
    # Bucket-level single-sweep update:
    #   fused_update(bucket_idx, param, grad, mix_partner, moments,
    #                *, step, alpha, layout=None, impl=None)
    #       -> (param', moments')
    # computing the gossip arrival mix (1-alpha)*param + alpha*mix_partner
    # followed by this optimizer's update at the mixed point, in ONE pass
    # over the bucket.  ``mix_partner=None`` (or alpha == 0) is the pure
    # local update.  ``moments`` is a tuple matching ``fused_moments`` (an
    # entry may be None, e.g. momentum-free sgd).  ``step`` is the int32
    # step counter (drives the lr schedule / bias corrections); ``layout``
    # the core.buckets.BucketLayout (needed by norm-based backends);
    # ``impl`` the kernel backend override (see kernels.ops).  None when
    # the optimizer has no fused backend.
    fused_update: Callable | None = None
    # False when the fused backend cannot run on shard-local (hierarchical)
    # bucket layouts — lars: its per-layer norm prepass would need a
    # cross-shard reduction inside shard_map. Such optimizers fall back to
    # the unfused mix-then-apply composition under fsdp/TP packing.
    fused_shard_local: bool = True


def sgd(schedule: Schedule | float, momentum: float = 0.9,
        weight_decay: float = 0.0) -> Optimizer:
    """SGD + momentum — the paper's optimizer (Caffe default momentum 0.9)."""
    sched = constant(schedule) if isinstance(schedule, (int, float)) else schedule

    def init(params):
        mom = jax.tree.map(jnp.zeros_like, params) if momentum else None
        return {"step": jnp.zeros((), jnp.int32), "mom": mom}

    def update(params, grads, state):
        lr = sched(state["step"])
        if weight_decay:
            grads = jax.tree.map(lambda g, p: g + weight_decay * p.astype(g.dtype),
                                 grads, params)
        if momentum:
            mom = jax.tree.map(lambda m, g: momentum * m + g.astype(m.dtype),
                               state["mom"], grads)
            params = jax.tree.map(
                lambda p, m: (p - lr * m.astype(jnp.float32)).astype(p.dtype),
                params, mom)
            return params, {"step": state["step"] + 1, "mom": mom}
        params = jax.tree.map(
            lambda p, g: (p - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return params, {"step": state["step"] + 1, "mom": None}

    def fused_update(bucket_idx, p, g, partner, moments, *, step, alpha,
                     layout=None, impl=None):
        from repro.kernels import fused_sgd_bucket
        (mom,) = moments
        new_p, new_m = fused_sgd_bucket(
            p, g, partner, mom, lr=sched(step), alpha=alpha,
            momentum=momentum, weight_decay=weight_decay, impl=impl)
        return new_p, (new_m,)

    return Optimizer(init, update, fused_moments=("mom",),
                     fused_update=fused_update)


def _lars_row_scale(layout, bucket_idx: int, p, g, partner, *, alpha: float,
                    weight_decay: float, trust_coef: float, eps: float):
    """LARS norm prepass for one bucket: per-layer trust ratios expanded to
    one fp32 scale per (row, 128) tile.

    Reads the mixed params ``(1-alpha)*p + alpha*partner`` (materialized to
    the bucket dtype, matching the standalone mix the unfused path would
    run) and the grads through the layout's static slot table — the exact
    slices ``PackedParams.unpack()`` serves — and computes
    ``trust = trust_coef * ||w|| / (||g + wd*w|| + wd*||w|| + eps)`` per
    layer, PER REPLICA ROW (each rank owns a distinct model).  Slot offsets
    are LANE-aligned, so every row belongs to exactly one slot; padding rows
    get scale 1.0 (their params/grads/moments are identically zero).
    """
    import numpy as np

    if getattr(layout, "num_shards", 1) > 1:
        raise ValueError(
            "lars has no fused backend for shard-local (hierarchical) "
            "layouts: the trust ratio needs per-LAYER norms, but inside "
            "shard_map each device holds only its own shard of every layer "
            "(a cross-shard norm reduction would break the single-sweep "
            "contract); use sgd/adamw, or lars with fused_update=False "
            "(its tree-level packed update reads global norms through the "
            "unpack view at the jit level)")
    # traced alpha (masked-alpha path of the bounded-delay runtime) always
    # mixes; only a static 0 drops the partner term from the prepass
    use_partner = partner is not None and not (
        isinstance(alpha, (int, float)) and alpha == 0.0)
    lane = layout.lane
    rows = int(p.shape[-2])
    slots = sorted((s for s in layout.slots if s.bucket == bucket_idx),
                   key=lambda s: s.offset)
    row_map = np.full((rows,), len(slots), np.int32)  # default: padding
    for k, s in enumerate(slots):
        row_map[s.offset // lane: -(-(s.offset + s.size) // lane)] = k
    row_map = jnp.asarray(row_map)

    def piece(x, s):
        """The slot's elements: its whole rows, then the slot's length."""
        r0, r1 = s.offset // lane, -(-(s.offset + s.size) // lane)
        return x[r0:r1].reshape(-1)[:s.size].astype(jnp.float32)

    def one_replica(pr, gr, br):
        trusts = []
        for s in slots:
            pf = piece(pr, s)
            if br is not None:
                pf = (pf * (1.0 - alpha) + piece(br, s) * alpha
                      ).astype(pr.dtype).astype(jnp.float32)
            gf = piece(gr, s)
            if weight_decay:
                gf = gf + weight_decay * pf
            wn = jnp.linalg.norm(pf.reshape(-1))
            gn = jnp.linalg.norm(gf.reshape(-1))
            trusts.append(jnp.where(
                (wn > 0) & (gn > 0),
                trust_coef * wn / (gn + weight_decay * wn + eps), 1.0))
        table = jnp.stack(trusts + [jnp.float32(1.0)])
        return table[row_map]

    lead = p.shape[:-2]
    pf2, gf2 = p.reshape((-1, rows, lane)), g.reshape((-1, rows, lane))
    if use_partner:
        bf2 = partner.reshape((-1, rows, lane))
        scale = jax.vmap(one_replica)(pf2, gf2, bf2)
    else:
        scale = jax.vmap(lambda a, b: one_replica(a, b, None))(pf2, gf2)
    return scale.reshape(lead + (rows,))


def lars(schedule: Schedule | float, momentum: float = 0.9,
         trust_coef: float = 1e-3, weight_decay: float = 0.0,
         eps: float = 1e-9) -> Optimizer:
    """Layer-wise Adaptive Rate Scaling [You et al., the paper's §8 pointer
    for large-batch hyperparameter scaling]: per-leaf LR is scaled by
    trust_coef * ||w|| / (||g|| + wd*||w||).

    Packed-aware: when the state is a core.buckets.PackedParams (bucketed
    gossip engine), the update reads per-LAYER norms through the
    ``unpack()`` slice views — the trust ratio never spans a bucket — and
    re-packs the results. The re-pack is one concatenate per bucket per
    step, a cost elementwise optimizers don't pay; it buys lars the packed
    engine's one-collective-per-bucket gossip path."""
    sched = constant(schedule) if isinstance(schedule, (int, float)) else schedule

    def init(params):
        return {"step": jnp.zeros((), jnp.int32),
                "mom": jax.tree.map(lambda p_: jnp.zeros_like(p_, jnp.float32),
                                    params)}

    def update(params, grads, state):
        from repro.core.buckets import PackedParams
        lr = sched(state["step"])

        def upd(p, g, m):
            pf = p.astype(jnp.float32)
            gf = g.astype(jnp.float32)
            if weight_decay:
                gf = gf + weight_decay * pf
            wn = jnp.linalg.norm(pf.reshape(-1))
            gn = jnp.linalg.norm(gf.reshape(-1))
            trust = jnp.where(
                (wn > 0) & (gn > 0),
                trust_coef * wn / (gn + weight_decay * wn + eps), 1.0)
            m = momentum * m + gf * trust
            return (pf - lr * m).astype(p.dtype), m

        packed = isinstance(params, PackedParams)
        if packed:
            layout = params.layout
            params, grads = params.unpack(), grads.unpack()
            mom = state["mom"].unpack()
        else:
            mom = state["mom"]
        out = jax.tree.map(upd, params, grads, mom)
        new_params = jax.tree.map(lambda o: o[0], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_mom = jax.tree.map(lambda o: o[1], out,
                               is_leaf=lambda x: isinstance(x, tuple))
        if packed:
            new_params = PackedParams(layout.pack(new_params), layout)
            new_mom = PackedParams(layout.pack(new_mom), layout)
        return new_params, {"step": state["step"] + 1, "mom": new_mom}

    def fused_update(bucket_idx, p, g, partner, moments, *, step, alpha,
                     layout=None, impl=None):
        """Two-phase fused LARS: a norm prepass reads the param/grad slices
        of THIS bucket through the layout's static slot table (the same
        slices ``PackedParams.unpack()`` serves) and produces one trust
        scalar per layer — computed per replica row, the distributed
        semantics (each rank owns a distinct model, paper §4) — expanded to
        a per-(row, 128)-tile scale; then the single-sweep kernel applies
        mix + momentum + trust-scaled step.  Unlike the tree-level packed
        update there is NO per-step re-pack concatenate."""
        from repro.kernels import dequant_tiles, fused_lars_bucket
        if layout is None:
            raise ValueError("lars.fused_update needs the BucketLayout for "
                             "its per-layer norm prepass")
        if isinstance(partner, dict):
            # quantized wire payload: pre-decode once — the norm prepass
            # reads the mixed params, so the decode cannot stay in-kernel
            # here; dequant-then-mix is bit-identical to in-kernel decode
            partner = dequant_tiles(partner["q"], partner["s"])
        (mom,) = moments
        scale = _lars_row_scale(
            layout, bucket_idx, p, g, partner, alpha=alpha,
            weight_decay=weight_decay, trust_coef=trust_coef, eps=eps)
        new_p, new_m = fused_lars_bucket(
            p, g, partner, mom, scale, lr=sched(step), alpha=alpha,
            momentum=momentum, weight_decay=weight_decay, impl=impl)
        return new_p, (new_m,)

    return Optimizer(init, update, elementwise=False, packed_aware=True,
                     fused_moments=("mom",), fused_update=fused_update,
                     fused_shard_local=False)


def adamw(schedule: Schedule | float, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8, weight_decay: float = 0.0) -> Optimizer:
    sched = constant(schedule) if isinstance(schedule, (int, float)) else schedule

    def init(params):
        return {"step": jnp.zeros((), jnp.int32),
                "m": jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params),
                "v": jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)}

    def update(params, grads, state):
        step = state["step"] + 1
        lr = sched(state["step"])
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
                         state["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g.astype(jnp.float32)),
                         state["v"], grads)
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, m_, v_):
            u = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
            if weight_decay:
                u = u + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * u).astype(p.dtype)

        params = jax.tree.map(upd, params, m, v)
        return params, {"step": step, "m": m, "v": v}

    def fused_update(bucket_idx, p, g, partner, moments, *, step, alpha,
                     layout=None, impl=None):
        from repro.kernels import fused_adamw_bucket
        m_, v_ = moments
        stepf = (step + 1).astype(jnp.float32)
        new_p, new_m, new_v = fused_adamw_bucket(
            p, g, partner, m_, v_, lr=sched(step),
            c1=1 - b1 ** stepf, c2=1 - b2 ** stepf, alpha=alpha, b1=b1,
            b2=b2, eps=eps, weight_decay=weight_decay, impl=impl)
        return new_p, (new_m, new_v)

    return Optimizer(init, update, fused_moments=("m", "v"),
                     fused_update=fused_update)
