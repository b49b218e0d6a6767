"""Shard-local (hierarchical) bucket layouts — fsdp-mode packed gossip.

Covers: the (leaf, shard_index) partition invariants (exact tiling, LANE
alignment per shard, uniform strides), pack/unpack roundtrip + packed
gradient transpose under in-replica sharding, spec construction and the
shard-aware layout/mesh guard, the lars fused-backend restriction,
checkpoint interchange between fsdp-packed / per-leaf / pure_dp-packed
states (the leaf-keyed on-disk format is layout-blind) plus staleness-ring
persistence under the shard-local layout (k=1 -> k=2 mask-pad), and
(subprocess, 8 forced host devices, mesh (pod=2, data=2, model=2)) the
acceptance oracle: fsdp-packed sync / async / fused trajectories fp32
BIT-identical to the per-leaf fsdp path and to core.simulate at p=2
replicas across all schedule phases, staleness k in {1, 2}, drops on/off —
plus an end-to-end fsdp --packed --fused-update train run against the
per-leaf path."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.buckets import (LANE, PackedParams, build_layout,
                                check_layout_mesh, packed_param_specs)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHARD_AXES = ("data", "model")
SHARD_SIZES = (2, 2)


def _tree(dtype=jnp.float32, lead=()):
    rng = np.random.default_rng(3)
    mk = lambda *s: jnp.asarray(rng.normal(size=lead + s),
                                jnp.float32).astype(dtype)
    return {
        "emb": mk(8, 6),        # dim0 FSDP-sharded over data
        "ffn": mk(4, 6, 11),    # dim0 TP-sharded over model
        "norm": mk(130,),       # fully replicated -> chunked over both axes
        "b": mk(1,),            # tiny replicated leaf (degenerate chunks)
    }


def _specs():
    return {"emb": P("data", None), "ffn": P("model", None, None),
            "norm": P(None), "b": P(None)}


def _hier_layout(tree, lead=()):
    return build_layout(tree, skip_leading=len(lead), shard_axes=SHARD_AXES,
                        shard_axis_sizes=SHARD_SIZES, shard_specs=_specs())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("lead", [(), (2,)])
def test_hier_pack_unpack_roundtrip(dtype, lead):
    tree = _tree(dtype, lead)
    layout = _hier_layout(tree, lead)
    assert layout.hierarchical and layout.num_shards == 4
    out = PackedParams.pack(tree, layout).unpack()
    for k in tree:
        np.testing.assert_array_equal(
            np.asarray(out[k], np.float32), np.asarray(tree[k], np.float32))


def test_hier_partition_invariants():
    """Pieces tile every leaf exactly once; every shard's offsets are
    LANE-aligned within its own stride; bucket totals = shards * stride."""
    tree = _tree()
    layout = _hier_layout(tree)
    sizes = {}
    for s in layout.slots:
        assert s.offset % LANE == 0
        assert s.offset + s.size <= layout.strides[s.bucket]
        assert layout.bucket_dtypes[s.bucket] == s.dtype
        sizes[s.index] = sizes.get(s.index, 0) + s.size
    leaves = jax.tree.leaves(tree)
    for i, leaf in enumerate(leaves):
        assert sizes[i] == int(np.prod(leaf.shape)), f"leaf {i} not tiled"
    for total, stride in zip(layout.bucket_sizes, layout.strides):
        assert total == stride * layout.num_shards
        assert stride % LANE == 0
    # no two slots of one shard overlap inside a bucket
    for b in range(layout.num_buckets):
        for s in range(layout.num_shards):
            spans = sorted((sl.offset, sl.offset + sl.size)
                           for sl in layout.slots
                           if sl.bucket == b and sl.shard == s)
            for (a0, a1), (b0, b1) in zip(spans, spans[1:]):
                assert a1 <= b0


def test_hier_gradients_arrive_packed():
    tree = _tree()
    layout = _hier_layout(tree)
    packed = PackedParams.pack(tree, layout)
    g = jax.grad(lambda q: sum(jnp.sum(l.astype(jnp.float32) ** 2)
                               for l in jax.tree.leaves(q.unpack())))(packed)
    assert isinstance(g, PackedParams)
    gu = g.unpack()
    for k in tree:
        np.testing.assert_allclose(np.asarray(gu[k]),
                                   2.0 * np.asarray(tree[k]), rtol=1e-5)


def test_no_shard_axes_reduces_to_flat_layout():
    """shard_axes=() must reproduce the PR-1 flat layout exactly (pure_dp
    packed trajectories are unchanged)."""
    tree = _tree()
    flat = build_layout(tree)
    also = build_layout(tree, shard_axes=(), shard_axis_sizes=())
    assert flat.bucket_sizes == also.bucket_sizes
    assert flat.strides == also.strides == flat.bucket_sizes
    assert [(s.index, s.bucket, s.offset, s.size) for s in flat.slots] == \
        [(s.index, s.bucket, s.offset, s.size) for s in also.slots]
    assert not flat.hierarchical


def test_hier_packed_param_specs():
    layout = _hier_layout(_tree())
    specs = packed_param_specs(layout, ("pod",))
    assert all(s == P("pod", ("data", "model"), None) for s in specs.buckets)
    # replica axes may not double as shard axes
    with pytest.raises(ValueError, match="shard"):
        packed_param_specs(layout, ("data",))


def test_check_layout_mesh_guard():
    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 2, "model": 2}

    layout = _hier_layout(_tree())
    check_layout_mesh(layout, FakeMesh())

    class WrongSize(FakeMesh):
        shape = {"pod": 2, "data": 4, "model": 2}

    with pytest.raises(ValueError, match="rebuild"):
        check_layout_mesh(layout, WrongSize())

    class MissingAxis(FakeMesh):
        axis_names = ("pod", "x")
        shape = {"pod": 2, "x": 2}

    with pytest.raises(ValueError, match="not in mesh"):
        check_layout_mesh(layout, MissingAxis())


def test_lars_fused_rejects_shard_local_layout():
    from repro.optim import lars
    opt = lars(0.1)
    assert not opt.fused_shard_local
    tree = _tree()
    layout = _hier_layout(tree)
    packed = PackedParams.pack(tree, layout)
    grads = PackedParams.pack(jax.tree.map(lambda x: x * 0.1, tree), layout)
    mom = PackedParams.pack(jax.tree.map(jnp.zeros_like, tree), layout)
    with pytest.raises(ValueError, match="shard-local"):
        opt.fused_update(0, packed.buckets[0], grads.buckets[0], None,
                         (mom.buckets[0],), step=jnp.int32(0), alpha=0.0,
                         layout=layout)


# --------------------------------------------------------------- checkpoints

def _flat_layout(tree):
    return build_layout(tree)


def test_checkpoint_interchange_hier_leaf_flat(tmp_path):
    """The on-disk format is leaf-keyed, so fsdp-packed / per-leaf /
    pure_dp-packed states all cross-restore each other's checkpoints."""
    from repro.checkpoint import restore_state, save_state
    tree = _tree(lead=(2,))
    hier = build_layout(tree, skip_leading=1, shard_axes=SHARD_AXES,
                        shard_axis_sizes=SHARD_SIZES, shard_specs=_specs())
    flat = build_layout(tree, skip_leading=1)
    states = {
        "hier": {"params": PackedParams.pack(tree, hier),
                 "opt": {"step": jnp.int32(7)}},
        "leaf": {"params": tree, "opt": {"step": jnp.int32(7)}},
        "flat": {"params": PackedParams.pack(tree, flat),
                 "opt": {"step": jnp.int32(7)}},
    }
    for src, src_state in states.items():
        d = str(tmp_path / f"ck_{src}")
        save_state(d, src_state, step=7)
        for dst, dst_state in states.items():
            rest, man = restore_state(d, dst_state)
            assert man["step"] == 7
            got = (rest["params"].unpack()
                   if isinstance(rest["params"], PackedParams)
                   else rest["params"])
            for k in tree:
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(tree[k]),
                                              err_msg=f"{src}->{dst}:{k}")


def test_ring_checkpoint_mask_pad_under_shard_local_layout(tmp_path):
    """A k=1 fsdp-packed ring checkpoint restores into a k=2 template:
    payload stays oldest, the new back slot starts invalid."""
    from repro.checkpoint import restore_state, save_state
    from repro.core.async_gossip import init_inbox_ring
    dp = 2
    tree = _tree(lead=(dp,))
    hier = build_layout(tree, skip_leading=1, shard_axes=SHARD_AXES,
                        shard_axis_sizes=SHARD_SIZES, shard_specs=_specs())
    packed = PackedParams.pack(tree, hier)
    ring1 = init_inbox_ring(packed, 1, dp)
    ring1 = dict(ring1, valid=jnp.ones((dp, 1), jnp.float32),
                 t=jnp.asarray(9, jnp.int32))
    state1 = {"params": packed, "opt": {"step": jnp.int32(9)},
              "inbox": ring1}
    d = str(tmp_path / "ck_ring")
    save_state(d, state1, step=9)

    template2 = {"params": packed, "opt": {"step": jnp.int32(0)},
                 "inbox": init_inbox_ring(packed, 2, dp)}
    rest, _ = restore_state(d, template2)
    ring2 = rest["inbox"]
    assert len(ring2["slots"]) == 2
    assert isinstance(ring2["slots"][0], PackedParams)
    # oldest slot carries the checkpointed payload, back slot is invalid
    up = ring2["slots"][0].unpack()
    for k in tree:
        np.testing.assert_array_equal(np.asarray(up[k]), np.asarray(tree[k]))
    np.testing.assert_array_equal(np.asarray(ring2["valid"]),
                                  np.asarray([[1.0, 0.0]] * dp, np.float32))
    assert int(ring2["t"]) == 9


# ------------------------------------------------- subprocess: the oracle

_ENGINE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import (build_schedule, make_gossip_mix,
                        make_packed_gossip_mix, gossip_mix_sim, build_layout,
                        PackedParams, make_async_gossip_mix,
                        make_packed_async_gossip_mix,
                        make_packed_fused_async_update,
                        make_packed_fused_update, gossip_mix_sim_delayed_k,
                        init_inbox_ring, exchange_ok)
from repro.kernels import gossip_mix_bucket
from repro.optim import sgd

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
p = 2
sched = build_schedule(p, num_rotations=2, seed=11)
rng = np.random.default_rng(2)
tree = {
    "emb": jnp.asarray(rng.normal(size=(p, 8, 6)), jnp.float32),
    "ffn": jnp.asarray(rng.normal(size=(p, 4, 6, 11)), jnp.float32),
    "norm": jnp.asarray(rng.normal(size=(p, 130)), jnp.float32),
    "b": jnp.asarray(rng.normal(size=(p, 1)), jnp.float32),
}
specs = {"emb": P("pod", "data", None), "ffn": P("pod", "model", None, None),
         "norm": P("pod", None), "b": P("pod", None)}
inner = {"emb": P("data", None), "ffn": P("model", None, None),
         "norm": P(None), "b": P(None)}
layout = build_layout(tree, skip_leading=1, shard_axes=("data", "model"),
                      shard_axis_sizes=(2, 2), shard_specs=inner)
assert layout.num_shards == 4

# sync: packed == per-leaf == simulator, bit-exact, every phase
pmix = make_packed_gossip_mix(
    mesh, ("pod",), sched, layout,
    mix_impl=lambda a, b, al: gossip_mix_bucket(a, b, al))
lmix = make_gossip_mix(mesh, ("pod",), sched, specs)
got_p = PackedParams.pack(tree, layout)
got_l = dict(tree); want = dict(tree)
for t in range(sched.period):
    got_p = pmix(got_p, t)
    got_l = lmix(got_l, t)
    want = gossip_mix_sim(want, jnp.asarray(sched.recv_from(t)))
    up = got_p.unpack()
    for k in tree:
        np.testing.assert_array_equal(np.asarray(up[k]), np.asarray(want[k]))
        np.testing.assert_array_equal(np.asarray(got_l[k]),
                                      np.asarray(want[k]))
print("ok sync")

# async ring: k in {1,2} x drops on/off, packed == per-leaf == oracle
for k_st in (1, 2):
    for rate in (0.0, 0.4):
        amix = make_packed_async_gossip_mix(
            mesh, ("pod",), sched, layout, staleness=k_st, drop_rate=rate,
            drop_seed=5,
            mix_impl=lambda a, b, al: gossip_mix_bucket(a, b, al))
        lamix = make_async_gossip_mix(
            mesh, ("pod",), sched, specs, staleness=k_st, drop_rate=rate,
            drop_seed=5)
        gp = PackedParams.pack(tree, layout); rp = init_inbox_ring(gp, k_st, p)
        gl = dict(tree); rl = init_inbox_ring(gl, k_st, p)
        ws = dict(tree); rs = init_inbox_ring(ws, k_st, p)
        for t in range(2 * sched.period):
            gp, rp = amix(gp, rp, t)
            gl, rl = lamix(gl, rl, t)
            ok = exchange_ok(rs["t"], jnp.arange(p), 5, rate)
            ws, rs = gossip_mix_sim_delayed_k(
                ws, rs, jnp.asarray(sched.recv_from(t % sched.period)),
                0.5, ok)
            up = gp.unpack()
            for kk in tree:
                np.testing.assert_array_equal(np.asarray(up[kk]),
                                              np.asarray(ws[kk]))
                np.testing.assert_array_equal(np.asarray(gl[kk]),
                                              np.asarray(ws[kk]))
        print(f"ok async k={k_st} rate={rate}")

# fused engines == oracle composition (sgd; pre-update partner algebra)
opt = sgd(0.1, momentum=0.9)
grads = jax.tree.map(lambda x: x * 0.1 + 0.01, tree)
gp = PackedParams.pack(grads, layout)
fup = make_packed_fused_update(mesh, ("pod",), sched, layout, opt, alpha=0.5)
params_f = PackedParams.pack(tree, layout); st_f = opt.init(params_f)
params_u = PackedParams.pack(tree, layout); st_u = opt.init(params_u)
for t in range(sched.period):
    params_f, st_f = fup(params_f, gp, st_f, t)
    recv_from = jnp.asarray(sched.recv_from(t))
    partner = jax.tree.map(lambda b: b[recv_from], params_u)
    mixed = jax.tree.map(
        lambda a, b: (a.astype(jnp.float32) * 0.5
                      + b.astype(jnp.float32) * 0.5).astype(a.dtype),
        params_u, partner)
    params_u, st_u = opt.update(mixed, gp, st_u)
    uf, uu = params_f.unpack(), params_u.unpack()
    for kk in tree:
        np.testing.assert_array_equal(np.asarray(uf[kk]), np.asarray(uu[kk]))
print("ok fused sync")

for k_st in (1, 2):
    for rate in (0.0, 0.4):
        fau = make_packed_fused_async_update(
            mesh, ("pod",), sched, layout, opt, alpha=0.5, staleness=k_st,
            drop_rate=rate, drop_seed=3)
        params_f = PackedParams.pack(tree, layout); st_f = opt.init(params_f)
        ring_f = init_inbox_ring(params_f, k_st, p)
        params_u = dict(tree); st_u = opt.init(params_u)
        ring_u = init_inbox_ring(params_u, k_st, p)
        for t in range(2 * sched.period):
            params_f, st_f, ring_f = fau(
                params_f, PackedParams.pack(grads, layout), ring_f, st_f, t)
            valid = ring_u["valid"]; a = 0.5 * valid[:, 0]
            mix = jax.tree.map(
                lambda x, b: x * (1 - a.reshape((-1,) + (1,) * (x.ndim - 1)))
                + b * a.reshape((-1,) + (1,) * (x.ndim - 1)),
                params_u, ring_u["slots"][0])
            recv_from = jnp.asarray(sched.recv_from(t % sched.period))
            payload = jax.tree.map(lambda q: q[recv_from], params_u)
            ok = exchange_ok(ring_u["t"], jnp.arange(p), 3, rate)
            ring_u = {"slots": tuple(ring_u["slots"][1:]) + (payload,),
                      "valid": jnp.concatenate([valid[:, 1:], ok[:, None]],
                                               1),
                      "t": ring_u["t"] + 1}
            params_u, st_u = opt.update(mix, grads, st_u)
            uf = params_f.unpack()
            for kk in tree:
                np.testing.assert_array_equal(np.asarray(uf[kk]),
                                              np.asarray(params_u[kk]))
        print(f"ok fused async k={k_st} rate={rate}")
print("ALL_OK")
"""


_E2E_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.data import ShardedTokenDataset
from repro.launch.mesh import make_smoke_mesh
from repro.launch.specs import train_input_specs
from repro.models import reduced
from repro.optim import sgd
from repro.train import (Trainer, init_train_state, make_distribution,
                         make_train_step_bundle)

cfg = dataclasses.replace(reduced(get_config("qwen3-0.6b"), d_model=64),
                          param_dtype="float32", compute_dtype="float32",
                          dist_mode="fsdp")
mesh = make_smoke_mesh(2, 2, pod=2)
dist = make_distribution(mesh, "fsdp")
assert dist.dp == 2 and dist.dp_axes == ("pod",)
assert dist.shard_axes == ("data", "model")
opt = sgd(0.3, momentum=0.9)
ss, sa, bs = train_input_specs(cfg, dist, 24, 4, opt)

runs = {}
for name, kw in (("leaf", dict(gossip_packed=False)),
                 ("packed_fused", dict(gossip_packed=True)),
                 ("packed_unfused", dict(gossip_packed=True,
                                         fused_update=False))):
    bundle = make_train_step_bundle(
        cfg, dist, opt, state_shapes=ss, state_axes=sa, batch_shapes=bs,
        protocol="gossip", remat=False, **kw)
    if kw.get("gossip_packed"):
        assert bundle.layout.num_shards == 4
        assert bundle.fused == (name == "packed_fused")
    state, _ = init_train_state(jax.random.key(0), cfg, dist, opt,
                                packed=kw.get("gossip_packed", False),
                                layout=bundle.layout)
    ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=24, n_shards=2,
                             batch_per_shard=2, seed=0)
    runs[name] = [h["loss"] for h in
                  Trainer(bundle, state, ds, log_every=0).run(6)]
    print(name, runs[name])

np.testing.assert_allclose(runs["leaf"], runs["packed_unfused"],
                           rtol=2e-4, atol=2e-4)
# fused shifts the partner term one update (PR-3 algebra) — close, not equal
np.testing.assert_allclose(runs["leaf"], runs["packed_fused"],
                           rtol=2e-2, atol=2e-2)
assert all(np.isfinite(v) for r in runs.values() for v in r)

# bounded-delay async on the hierarchical layout trains end to end
bundle = make_train_step_bundle(
    cfg, dist, opt, state_shapes=ss, state_axes=sa, batch_shapes=bs,
    protocol="gossip_async", staleness=2, drop_rate=0.3, remat=False,
    gossip_packed=True)
state, _ = init_train_state(jax.random.key(0), cfg, dist, opt, packed=True,
                            layout=bundle.layout,
                            inbox=bundle.protocol.staleness)
ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=24, n_shards=2,
                         batch_per_shard=2, seed=0)
hist = Trainer(bundle, state, ds, log_every=0).run(6)
assert all(np.isfinite(h["loss"]) for h in hist)
print("ALL_OK")
"""


def _run_sub(script: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL_OK" in r.stdout
    return r.stdout


@pytest.mark.slow
def test_hier_engines_match_oracle_all_phases():
    out = _run_sub(_ENGINE_SCRIPT)
    assert "ok fused async k=2 rate=0.4" in out


@pytest.mark.slow
def test_fsdp_packed_trains_end_to_end():
    _run_sub(_E2E_SCRIPT)
