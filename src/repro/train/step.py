"""Protocol-neutral distributed train step.

Replica representation: every param / optimizer-state leaf carries a leading
replica axis of size ``dist.dp`` sharded over the gossip axes; the batch is
``(dp, local_b, ...)`` replica-major. The per-replica gradient is a vmap over
that axis — so *no* cross-replica reduction exists unless the protocol
inserts one (AGD's mean == all-reduce; GossipGraD's mix == collective-permute;
none == ensemble). This reproduces the paper's semantics exactly: each rank
owns a distinct model, communication is whatever the protocol says.

Step layout (mirrors GossipGraD Fig. 8/9):
    1. per-replica grads from the LOCAL batch shard          (compute)
    2. protocol.comm_grads      — AGD's all-reduce           (comm, overlapped)
    3. local optimizer update                                 (compute)
    4. protocol.comm_params     — gossip ppermute + average  (comm, overlapped)
    5. ring-rotate the *next* batch shards (§4.5.2 shuffle)  (comm, overlapped)

``gossip_async`` (§4.2/§5, core.async_gossip) reorders this: the train
state carries a staleness-k **inbox ring** (the last k in-flight exchanges,
oldest first, each with a landed/valid flag), the masked arrival mix of the
oldest slot + the outgoing ppermute run *before* step (1), and the
transfer's result is only needed k steps later — so XLA overlaps the wire
with k whole forward/backwards instead of exposing it after the update, and
an exchange that misses its deadline is simply skipped (alpha = 0 for that
slot — the paper's unreliable-exchange premise).

``phase`` (the gossip schedule position) is STATIC by default: the launcher
keeps ``schedule.period`` compiled variants — see core/gossip.py for the
rationale and the dynamic lax.switch alternative.

**Fused mix+apply** (default for packed states whose optimizer exposes a
``fused_update`` backend): the gossip mix and the optimizer update collapse
into ONE single-sweep kernel per bucket (kernels/fused_update.py via
core.gossip.make_packed_fused_update / core.async_gossip.
make_packed_fused_async_update), so the update path makes one fused read
pass and one fused write pass over the parameter state instead of the mix
pass plus 2-3 optimizer passes.  The sync-gossip fused step dispatches
``ppermute(params)`` at the top of the program (partner's pre-update params
— the GoSGD-style combined update; the wire overlaps the whole fwd/bwd) and
non-gossip phases run the same kernel with alpha=0, keeping one compiled
step body shape per phase.

NOTE the fused default changes the dp>1 gossip ALGEBRA, not just its cost:
the partner term is one update staler than the PR-1 synchronous
post-update average (the same staleness §5's asynchrony embraces — the
mixing matrix, mean preservation, and diffusion analysis are unchanged),
and gradients are evaluated at the pre-mix params.  At dp == 1 (and for
agd/every_logp/none) the fused step is bit-identical to the unfused one.
``fused_update=False`` keeps the PR-1/2 mix-then-apply composition
bit-for-bit at any dp.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import make_protocol, make_ring_shuffle
from repro.core.async_gossip import (inbox_ring_specs, init_inbox_ring,
                                     init_wire_inbox_ring,
                                     wire_inbox_ring_specs)
from repro.core.buckets import PackedParams, build_layout, packed_param_specs
from repro.dist_ctx import use_distribution
from repro.models import lm_init
from repro.models.attention import count_attn_paths
from repro.models.moe import count_moe_layers
from repro.models.config import ModelConfig
from repro.optim import Optimizer
from .loss import make_loss_fn
from .sharding import Distribution

PyTree = Any

__all__ = ["TrainStepBundle", "make_train_step_bundle", "init_train_state"]


class TrainStepBundle:
    def __init__(self, *, step_fn, state_specs, batch_specs, protocol, dist,
                 cfg, optimizer, attn_paths, moe_layers, layout=None,
                 fused=False, wire=None):
        self.step_fn = step_fn          # (state, batch, *, phase:int static)
        self.state_specs = state_specs
        self.batch_specs = batch_specs
        self.protocol = protocol
        self.dist = dist
        self.cfg = cfg
        self.optimizer = optimizer
        self.layout = layout            # BucketLayout when gossip_packed
        self.fused = fused              # single-sweep fused mix+apply engine
        self.wire = wire                # WireFormat when compressed/sampled
        # attention call sites by path in the last traced step
        # (models.attention.count_attn_paths)
        self.attn_paths = attn_paths
        # expert layer sites of the last traced step
        # (models.moe.count_moe_layers)
        self.moe_layers = moe_layers

    @property
    def state_shardings(self):
        return jax.tree.map(self.dist.sharding, self.state_specs)

    @property
    def batch_shardings(self):
        return jax.tree.map(self.dist.sharding, self.batch_specs)

    def jitted(self, phase: int, donate: bool = True):
        fn = functools.partial(self.step_fn, phase=phase)
        return jax.jit(
            fn,
            in_shardings=(self.state_shardings, self.batch_shardings),
            out_shardings=(self.state_shardings, self.batch_shardings, None),
            donate_argnums=(0, 1) if donate else ())


def _replicate_tree(tree: PyTree, dp: int) -> PyTree:
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (dp,) + x.shape), tree)


def init_train_state(key, cfg: ModelConfig, dist: Distribution,
                     optimizer: Optimizer, *, packed: bool = False,
                     layout=None, inbox: int = 0, wire=None):
    """(state, state_axes): state = {"params","opt"}, leaves carry a leading
    replica axis of size dist.dp (1 in single-pod fsdp mode).

    ``packed=True`` stores params (and hence optimizer state) as
    core.buckets.PackedParams — the one-time pack of the bucketed gossip
    engine. Pass the bundle's ``layout`` so state and step agree. The
    returned ``state_axes`` always annotate the UNPACKED leaf tree (packed
    state derives its specs from the layout via packed_param_specs, not from
    axes).

    ``inbox`` is the inbox-ring depth (pass the bundle's
    ``protocol.staleness``; 0 = no ring): gossip_async with dp > 1 carries a
    staleness-k ring bootstrapped all-invalid ("nothing received yet"), so
    the first k arrival mixes are skips.

    ``wire`` (pass the bundle's ``.wire``; None = the uncompressed wire)
    switches the ring slots to compressed wire payloads — codes + scales
    zero-initialized, consumed only at alpha = 0 until real dispatches
    land."""
    params, axes = lm_init(key, cfg)
    params = _replicate_tree(params, max(dist.dp, 1))
    if packed:
        if layout is None and dist.shard_axes:
            raise ValueError(
                "this distribution shards inside a replica "
                f"(axes {dist.shard_axes}); packed init needs the bundle's "
                "shard-local layout — pass layout=bundle.layout")
        # materialise the leaves before the pack: under jit, XLA:TPU fuses
        # the random init into the bucket concatenates, and for qwen3-0.6b
        # that one program compiles to 0.93 GB of code in 211 s (19 s and
        # 8 MB with the barrier, compiled for a described v5e)
        params = jax.lax.optimization_barrier(params)
        params = (PackedParams.pack(params, skip_leading=1) if layout is None
                  else PackedParams.pack(params, layout))
    axes = jax.tree.map(lambda s: "," + s, axes)
    opt_state = optimizer.init(params)
    state = {"params": params, "opt": opt_state}
    if inbox:
        if wire is not None and not wire.is_default:
            if not packed:
                raise ValueError("the compressed wire needs packed state")
            state["inbox"] = init_wire_inbox_ring(params, int(inbox),
                                                  max(dist.dp, 1), wire)
        else:
            state["inbox"] = init_inbox_ring(params, int(inbox),
                                             max(dist.dp, 1))
    return state, axes


def state_specs_of(dist: Distribution, state_shapes: PyTree,
                   state_axes: PyTree, param_specs: PyTree = None) -> PyTree:
    if param_specs is None:
        param_specs = dist.param_specs(state_shapes["params"], state_axes,
                                       replica_axis=True)
    opt_specs = {}
    for k, v in state_shapes["opt"].items():
        if k == "step":
            opt_specs[k] = P()
        elif v is None:
            opt_specs[k] = None
        else:
            opt_specs[k] = param_specs
    return {"params": param_specs, "opt": opt_specs}


def make_train_step_bundle(
    cfg: ModelConfig,
    dist: Distribution,
    optimizer: Optimizer,
    *,
    state_shapes: PyTree,
    state_axes: PyTree,
    batch_shapes: PyTree,
    protocol: str = "gossip",
    topology: str = "dissemination",
    num_rotations: int = 2,
    gossip_mode: str = "static",
    gossip_packed: bool = False,
    gossip_alpha: float = 0.5,
    staleness: int = 1,
    drop_rate: float = 0.0,
    drop_seed: int = 0,
    wire_dtype: str = "fp32",
    gossip_subset: float = 1.0,
    wire_seed: int = 0,
    fused_update: Optional[bool] = None,
    fused_impl: Optional[str] = None,
    mix_impl: Optional[Callable] = None,
    rotate_samples: Optional[bool] = None,
    remat: bool = True,
    remat_policy=None,
    ssm_scan_impl=None,
    seed: int = 0,
) -> TrainStepBundle:
    """Build the train step for (cfg, mesh, protocol). ``state_shapes`` /
    ``batch_shapes`` are ShapeDtypeStruct trees (e.g. from jax.eval_shape) so
    nothing is materialized — the dry-run path.

    ``gossip_packed=True`` runs the bucketed persistent-buffer engine: params
    and optimizer state live in LANE-aligned dtype-homogeneous buckets
    (core.buckets) packed once at init; the forward reads through unpack
    views, autodiff delivers gradients already packed, and the gossip mix is
    one ppermute + in-place Pallas mix per bucket. ELEMENTWISE optimizers
    (sgd, adamw) are packed-transparent; norm-based optimizers must declare
    ``packed_aware`` and read their per-leaf norms through the
    ``PackedParams.unpack()`` view (lars does).  Distributions that shard
    inside a replica (fsdp's FSDP+TP, replica-mode tensor parallelism) get a
    SHARD-LOCAL layout: each (data, model) position packs its own shard
    bytes into the buckets, the bucket rows shard over
    ``dist.shard_axes``, and gossip still ppermutes over the replica axes
    only — the hierarchical GossipGraD regime (pods gossip, each pod holds
    one sharded copy).

    ``staleness`` (gossip_async only) is the inbox-ring depth k — the
    bounded delay of the async runtime: the exchange dispatched at step t
    is consumed at step t + k, so the wire has k full steps to land.
    ``drop_rate`` injects emulated-wire timeout drops (skip-on-timeout)
    through the deterministic ``core.async_gossip.exchange_ok`` hash seeded
    by ``drop_seed``.

    ``wire_dtype`` ("fp32"/"bf16"/"int8"/"fp8") and ``gossip_subset``
    configure the compressed + partition-sampled gossip wire
    (kernels.quantize.WireFormat): int8/fp8 payloads are stochastic-rounded
    on dispatch (hash seeded by ``wire_seed``, independent of the drop
    seed) and decoded inside the arrival-mix / fused-update sweep, and
    ``gossip_subset < 1`` ships only a rotating subset of buckets per
    exchange (unsent buckets skip at alpha = 0). Requires
    ``gossip_packed=True``; the fp32 full-participation default is the
    exact PR-1..5 code path.

    ``fused_update`` (default None = auto: on when packed and the optimizer
    exposes a ``fused_update`` backend) collapses mix + optimizer update
    into one single-sweep kernel per bucket; at dp > 1 this also shifts the
    gossip partner term one update staler (GoSGD-style combined update) —
    see the module docstring, and pass ``fused_update=False`` to reproduce
    PR-1/2 trajectories exactly.  ``fused_impl`` forces the kernel backend
    ("pallas"/"jnp", see kernels.ops)."""
    mesh = dist.mesh
    if rotate_samples is None:
        rotate_samples = protocol in ("gossip", "gossip_async")

    from repro.kernels.quantize import WireFormat
    wire_fmt = WireFormat(dtype=wire_dtype, subset=gossip_subset,
                          seed=wire_seed)
    wired = (not wire_fmt.is_default
             and protocol in ("gossip", "gossip_async"))
    if wired and not gossip_packed:
        raise ValueError(
            "the compressed/partition-sampled wire (wire_dtype="
            f"{wire_dtype!r}, gossip_subset={gossip_subset}) needs "
            "gossip_packed=True — the per-leaf path has no lane-aligned "
            "buckets to quantize over")

    state_specs = state_specs_of(dist, state_shapes, state_axes)
    param_specs = state_specs["params"]
    batch_specs = jax.tree.map(
        lambda x: dist.replica_batch_spec(x.ndim), batch_shapes)

    layout = None
    if gossip_packed:
        if not (getattr(optimizer, "elementwise", True)
                or getattr(optimizer, "packed_aware", False)):
            raise ValueError(
                "gossip_packed requires an elementwise or packed-aware "
                "optimizer: this one computes per-leaf norms without reading "
                "through the PackedParams.unpack() view, so they would span "
                "whole buckets instead of layers; use sgd/adamw/lars or the "
                "per-leaf gossip path")
        layout = _build_packed_layout(dist, state_shapes["params"],
                                      param_specs)
        packed_shapes = jax.eval_shape(
            lambda t: PackedParams(layout.pack(t), layout),
            state_shapes["params"])
        opt_shapes = jax.eval_shape(optimizer.init, packed_shapes)
        state_shapes = {"params": packed_shapes, "opt": opt_shapes}
        param_specs = packed_param_specs(layout, dist.dp_axes)
        state_specs = state_specs_of(dist, state_shapes, state_axes,
                                     param_specs=param_specs)
        if mix_impl is None:  # donation-friendly Pallas bucket mix
            from repro.kernels import gossip_mix_bucket, gossip_mix_wire_bucket
            # the wire-aware wrapper decodes quantized payloads inside the
            # same sweep; on raw payloads it IS gossip_mix_bucket
            mix_impl = gossip_mix_wire_bucket if wired else gossip_mix_bucket

    shard_local_ok = (layout is None or layout.num_shards == 1
                      or getattr(optimizer, "fused_shard_local", True))
    if fused_update is None:
        fused_update = (gossip_packed and optimizer.fused_update is not None
                        and shard_local_ok)
    if fused_update and not gossip_packed:
        raise ValueError("fused_update needs the bucketed engine: pass "
                         "gossip_packed=True")
    if fused_update and optimizer.fused_update is None:
        raise ValueError(
            "fused_update=True but this optimizer has no fused backend; "
            "use sgd/adamw/lars or fused_update=False")
    if fused_update and not shard_local_ok:
        raise ValueError(
            "fused_update=True but this optimizer's fused backend does not "
            "support shard-local (hierarchical) bucket layouts; use "
            "sgd/adamw or fused_update=False")

    proto = make_protocol(
        protocol, mesh, dist.dp_axes, param_specs,
        topology=topology, num_rotations=num_rotations, alpha=gossip_alpha,
        staleness=staleness, drop_rate=drop_rate, drop_seed=drop_seed,
        mode=gossip_mode, mix_impl=mix_impl,
        packed_layout=layout, seed=seed,
        wire_dtype=wire_dtype, gossip_subset=gossip_subset,
        wire_seed=wire_seed)

    fused_eng = None
    if fused_update:
        from repro.core.async_gossip import make_packed_fused_async_update
        from repro.core.gossip import make_packed_fused_update
        if proto.staleness > 0:
            fused_eng = make_packed_fused_async_update(
                mesh, dist.dp_axes, proto.schedule, layout, optimizer,
                alpha=gossip_alpha, staleness=proto.staleness,
                drop_rate=drop_rate, drop_seed=drop_seed,
                mode=gossip_mode, impl=fused_impl, wire=proto.wire)
        elif protocol == "gossip" and proto.dp > 1:
            fused_eng = make_packed_fused_update(
                mesh, dist.dp_axes, proto.schedule, layout, optimizer,
                alpha=gossip_alpha, mode=gossip_mode, impl=fused_impl,
                wire=proto.wire)
        else:
            # non-gossip phases (agd / every_logp / none) and dp == 1 run
            # the same single-sweep kernel with alpha = 0
            fused_eng = make_packed_fused_update(
                mesh, dist.dp_axes, None, layout, optimizer,
                alpha=0.0, mode=gossip_mode, impl=fused_impl)

    if proto.staleness > 0:
        # the staleness-k inbox ring rides in the train state: k slots with
        # the params' shapes and sharding (wire payloads — codes + scales —
        # under a compressed wire), the per-slot validity mask, and the
        # dispatch counter (all checkpointed with the state)
        if proto.wire is not None:
            state_specs = dict(state_specs, inbox=wire_inbox_ring_specs(
                param_specs, dist.dp_axes, proto.staleness, proto.wire))
        else:
            state_specs = dict(state_specs, inbox=inbox_ring_specs(
                param_specs, dist.dp_axes, proto.staleness))

    # per-layer remat happens inside the stack (blocks.stack_apply) — the
    # whole-loss checkpoint variant kept 130+GB of scan residuals alive.
    loss_fn = make_loss_fn(cfg, ssm_scan_impl=ssm_scan_impl, remat=remat,
                           remat_policy=remat_policy)

    def replica_loss(params_one, batch_one):
        # the scope names the forward; JAX names its backward
        # (transpose(...)) and the remat recompute (rematted_computation)
        with jax.named_scope("fwd"):
            if gossip_packed:
                # loss over the buckets: unpack is slice+reshape views fused
                # into the forward, and its autodiff transpose packs the
                # gradients for free
                params_one = params_one.unpack()
            return loss_fn(params_one, batch_one)

    # the replica axis is sharded over the dp axes: say so to the per-replica
    # shard_maps inside (the flash attention call), so none gathers it
    grad_fn = jax.vmap(jax.value_and_grad(replica_loss, has_aux=True),
                       spmd_axis_name=dist.dp_axes or None)

    shuffle = None
    if rotate_samples and dist.dp > 1:
        shuffle = make_ring_shuffle(mesh, dist.dp_axes, batch_specs)

    attn_paths: Dict[str, int] = {}
    moe_layers: list = []

    def train_step(state, batch, *, phase: int):
      with use_distribution(dist), count_attn_paths() as paths, \
              count_moe_layers() as moe_sites:
        params = state["params"]
        batch = jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, dist.sharding(s)),
            batch, batch_specs)
        new_inbox = None
        if fused_eng is not None:
            # fused mix+apply: grads at the incoming params, then ONE
            # single-sweep kernel per bucket does arrival mix + optimizer
            # update (the engine dispatches its ppermute at the program top,
            # so the wire overlaps this fwd/bwd).
            (_, metrics), grads = grad_fn(params, batch)
            with jax.named_scope("exchange"):
                grads = proto.comm_grads(grads, phase)
            with jax.named_scope("update"):
                if proto.staleness > 0:
                    new_params, new_opt, new_inbox = fused_eng(
                        params, grads, state["inbox"], state["opt"], phase)
                else:
                    new_params, new_opt = fused_eng(params, grads,
                                                    state["opt"], phase)
            if proto.staleness == 0 and proto.name == "every_logp":
                # the periodic model all-reduce stays a separate
                # (amortized-O(1/log p)) pass
                with jax.named_scope("exchange"):
                    new_params = proto.comm_params(new_params, phase)
        else:
            if proto.staleness > 0:
                # bounded-delay arrival: masked-mix the oldest ring slot
                # into the params (a dropped slot skips), then re-dispatch
                # immediately. The ppermute's result is consumed only k
                # steps later, so the wire transfer overlaps the entire
                # forward/backward below (and the next k-1 whole steps).
                with jax.named_scope("exchange"):
                    params, new_inbox = proto.comm_params(
                        params, phase, inbox=state["inbox"])
            (_, metrics), grads = grad_fn(params, batch)
            with jax.named_scope("exchange"):
                grads = proto.comm_grads(grads, phase)
            with jax.named_scope("update"):
                new_params, new_opt = optimizer.update(params, grads,
                                                       state["opt"])
            if proto.staleness == 0:
                with jax.named_scope("exchange"):
                    new_params = proto.comm_params(new_params, phase)
        new_params = jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, dist.sharding(s)),
            new_params, param_specs)
        next_batch = batch
        if shuffle is not None:
            with jax.named_scope("shuffle"):
                next_batch = shuffle(batch)
        metrics = jax.tree.map(lambda m: m.mean(), metrics)
        new_state = {"params": new_params, "opt": new_opt}
        if proto.staleness > 0:
            new_state["inbox"] = new_inbox
        attn_paths.update(paths)
        moe_layers[:] = moe_sites
        return new_state, next_batch, metrics

    return TrainStepBundle(
        step_fn=train_step, state_specs=state_specs, batch_specs=batch_specs,
        protocol=proto, dist=dist, cfg=cfg, optimizer=optimizer,
        layout=layout, fused=fused_update, wire=proto.wire,
        attn_paths=attn_paths, moe_layers=moe_layers)


def _build_packed_layout(dist: Distribution, param_shapes: PyTree,
                         param_specs: PyTree):
    """Shard-aware successor of the old "only sharded on the replica axis"
    guard: distributions that shard nothing inside a replica (pure_dp /
    smoke) get the flat PR-1 layout; distributions that do (fsdp's FSDP+TP,
    replica-mode tensor parallelism) get a SHARD-LOCAL layout keyed by
    (leaf, shard_index) — each in-replica mesh position packs its own shard
    bytes, and the bucket rows shard over ``dist.shard_axes``. A spec
    that uses a replica axis beyond the leading dim is still rejected (it
    would alias replica bytes into the shard partition)."""
    from jax.sharding import PartitionSpec
    is_spec = lambda x: isinstance(x, PartitionSpec)
    for spec in jax.tree.leaves(param_specs, is_leaf=is_spec):
        if not is_spec(spec):
            continue
        for dim in tuple(spec)[1:]:
            axes = dim if isinstance(dim, tuple) else (dim,) if dim else ()
            for ax in axes:
                if ax in dist.dp_axes and dist.mesh.shape[ax] != 1:
                    raise ValueError(
                        f"a non-leading param dim is sharded on replica "
                        f"axis {ax!r}; the packed engine cannot represent "
                        "this — keep the per-leaf gossip path")
    if not dist.shard_axes:
        return build_layout(param_shapes, skip_leading=1)

    def inner(spec):
        # drop size-1 mesh axes: they shard nothing and are not part of the
        # layout's shard decomposition
        dims = []
        for dim in tuple(spec)[1:]:
            axes = dim if isinstance(dim, tuple) else (dim,) if dim else ()
            kept = tuple(a for a in axes if a in dist.shard_axes)
            dims.append(kept if len(kept) > 1 else kept[0] if kept else None)
        return PartitionSpec(*dims)

    inner_specs = jax.tree.map(inner, param_specs, is_leaf=is_spec)
    return build_layout(param_shapes, skip_leading=1,
                        shard_axes=dist.shard_axes,
                        shard_axis_sizes=dist.shard_axis_sizes,
                        shard_specs=inner_specs)
