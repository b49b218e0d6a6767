"""Bucketed gossip engine: layout invariants, pack/unpack roundtrip,
PackedParams-as-pytree behavior, checkpoint format stability, packed-vs-leaf
training equivalence, and (subprocess, 8 forced host devices) mix equivalence
bucketed == per-leaf == simulator across every schedule phase of p=8 for
bf16 and fp32 with odd leaf sizes."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.buckets import (LANE, BucketLayout, PackedParams,
                                build_layout, packed_param_specs)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _odd_tree(dtype, lead=()):
    rng = np.random.default_rng(7)
    mk = lambda *s: jnp.asarray(rng.normal(size=lead + s), jnp.float32).astype(dtype)
    return {"w1": mk(5, 3), "w2": mk(130,), "w3": mk(2, 7, 11), "b": mk(1,)}


def _layout(tree, lead, kind):
    """A flat layout, or a shard-local one over a (data 2, model 2) replica
    (``w3`` sharded on its first dim, the rest chunked over both axes)."""
    if kind == "flat":
        return build_layout(tree, skip_leading=len(lead))
    from jax.sharding import PartitionSpec as P
    return build_layout(
        tree, skip_leading=len(lead), shard_axes=("data", "model"),
        shard_axis_sizes=(2, 2),
        shard_specs={"w1": None, "w2": None, "w3": P("data"), "b": None})


@pytest.mark.parametrize("kind", ["flat", "shard_local"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("lead", [(), (4,)])
def test_pack_unpack_roundtrip(dtype, lead, kind):
    tree = _odd_tree(dtype, lead)
    layout = _layout(tree, lead, kind)
    packed = PackedParams.pack(tree, layout)
    # every bucket is stored as the kernels' (rows, 128) view
    for i, (b, n) in enumerate(zip(packed.buckets, layout.bucket_sizes)):
        assert b.shape == lead + layout.bucket_shape(i) == lead + (
            n // LANE, LANE)
    out = packed.unpack()
    for k in tree:
        np.testing.assert_array_equal(
            np.asarray(out[k], np.float32), np.asarray(tree[k], np.float32))


@pytest.mark.parametrize("kind", ["flat", "shard_local"])
def test_bucket_sizes_count_elements(kind):
    """``bucket_sizes`` stays each bucket's element count, the count the
    update kernel's byte model reads: the storage's rows times the lane."""
    tree = _odd_tree(jnp.float32, (4,))
    layout = _layout(tree, (4,), kind)
    if kind == "flat":
        # w1 15 -> 128, w2 130 -> 256, w3 154 -> 256, b 1 -> 128
        assert layout.bucket_sizes == (768,)
    else:
        assert layout.bucket_sizes == tuple(4 * s for s in layout.strides)
    packed = PackedParams.pack(tree, layout)
    assert [int(np.prod(b.shape[1:])) for b in packed.buckets] == list(
        layout.bucket_sizes)


def test_layout_invariants():
    tree = {**_odd_tree(jnp.float32), "h": jnp.zeros((300,), jnp.bfloat16)}
    layout = build_layout(tree)
    for s in layout.slots:
        assert s.offset % LANE == 0
        assert layout.bucket_dtypes[s.bucket] == s.dtype  # dtype-homogeneous
    for n in layout.bucket_sizes:
        assert n % LANE == 0 and n > 0
    assert sorted(set(layout.bucket_dtypes)) == ["bfloat16", "float32"]
    s = layout.summary()
    assert s["padded_bytes"] >= s["exact_bytes"]


def test_layout_balances_buckets():
    # 8 equal leaves forced into 2 buckets: greedy must split them 4/4
    tree = {f"l{i}": jnp.zeros((LANE * 4,)) for i in range(8)}
    layout = build_layout(tree, target_bucket_bytes=LANE * 4 * 4 * 4)
    assert layout.num_buckets == 2
    assert layout.bucket_sizes[0] == layout.bucket_sizes[1]


@pytest.mark.parametrize("kind", ["flat", "shard_local"])
def test_packed_params_is_elementwise_pytree(kind):
    tree = _odd_tree(jnp.float32)
    packed = PackedParams.pack(tree, _layout(tree, (), kind))
    doubled = jax.tree.map(lambda x: x * 2.0, packed)
    assert isinstance(doubled, PackedParams)
    out = doubled.unpack()
    np.testing.assert_allclose(np.asarray(out["w2"]),
                               2.0 * np.asarray(tree["w2"]), rtol=1e-6)
    # gradients w.r.t. the buckets arrive packed, in the buckets' storage
    # shape, and equal to the leaves' gradients packed — no per-step concat
    g = jax.grad(lambda p: sum(jnp.sum(l.astype(jnp.float32) ** 2)
                               for l in jax.tree.leaves(p.unpack())))(packed)
    assert isinstance(g, PackedParams)
    assert [b.shape for b in g.buckets] == [b.shape for b in packed.buckets]
    for a, b in zip(g.buckets, jax.tree.map(lambda x: 2.0 * x,
                                            packed).buckets):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    jaxpr = str(jax.make_jaxpr(
        lambda p: jax.tree.map(lambda x: x * 0.5, p))(packed))
    assert "concatenate" not in jaxpr


def test_packed_param_specs_structure():
    from jax.sharding import PartitionSpec as P
    layout = build_layout(_odd_tree(jnp.float32, (4,)), skip_leading=1)
    specs = packed_param_specs(layout, ("data",))
    assert isinstance(specs, PackedParams)
    assert all(s == P("data", None, None) for s in specs.buckets)


def test_checkpoint_roundtrip_and_cross_format(tmp_path):
    from repro.checkpoint import restore_state, save_state
    tree = _odd_tree(jnp.float32)
    packed_state = {"params": PackedParams.pack(tree),
                    "opt": {"step": jnp.int32(3)}}
    leaf_state = {"params": tree, "opt": {"step": jnp.int32(0)}}
    d = str(tmp_path / "ck")
    save_state(d, packed_state, step=3)
    # packed -> packed
    rest, man = restore_state(d, packed_state)
    assert isinstance(rest["params"], PackedParams)
    np.testing.assert_array_equal(np.asarray(rest["params"].unpack()["w2"]),
                                  np.asarray(tree["w2"]))
    # the on-disk format is leaf-keyed: a leaf engine restores it directly
    rest2, _ = restore_state(d, leaf_state)
    np.testing.assert_array_equal(np.asarray(rest2["params"]["w2"]),
                                  np.asarray(tree["w2"]))
    # and a leaf checkpoint restores into a packed template
    d2 = str(tmp_path / "ck2")
    save_state(d2, leaf_state, step=0)
    rest3, _ = restore_state(d2, packed_state)
    assert isinstance(rest3["params"], PackedParams)
    np.testing.assert_array_equal(np.asarray(rest3["params"].unpack()["w3"]),
                                  np.asarray(tree["w3"]))


def test_checkpoint_with_flat_buckets_resumes_bit_identically(tmp_path):
    """A checkpoint that holds bucket-shaped state flat, ``(dp, rows *
    128)`` — the int8 wire ring's payload codes, as written before buckets
    were stored ``(dp, rows, 128)`` — restores by a reshape, and the run
    resumed from it is bit-identical to the one that never stopped."""
    import json

    from repro.checkpoint import restore_state, save_state
    from repro.core.async_gossip import init_wire_inbox_ring
    from repro.core.simulate import gossip_mix_sim_quantized_k
    from repro.kernels.quantize import WireFormat

    p, k = 4, 2
    wire = WireFormat(dtype="int8", seed=5)
    tree = _odd_tree(jnp.float32, (p,))
    packed = PackedParams.pack(tree, build_layout(tree, skip_leading=1))
    recv = jnp.asarray([1, 2, 3, 0])
    step = jax.jit(lambda s: (lambda b, r: {
        "params": PackedParams(b, packed.layout), "inbox": r})(
        *gossip_mix_sim_quantized_k(list(s["params"].buckets), s["inbox"],
                                    recv, wire=wire)))
    state = {"params": packed,
             "inbox": init_wire_inbox_ring(packed, k, p, wire)}
    for _ in range(k + 1):            # real payloads in every slot
        state = step(state)
    d = tmp_path / "ck"
    save_state(str(d), state, step=k + 1)
    # rewrite the checkpoint as the flat-bucket store wrote it
    man = json.loads((d / "manifest.json").read_text())
    arrays = dict(np.load(d / "arrays.npz"))
    flat = 0
    for i, key in enumerate(man["keys"]):
        a = arrays[f"a{i}"]
        if a.ndim == 3 and a.shape[-1] == LANE:
            arrays[f"a{i}"] = a.reshape(a.shape[0], -1)
            man["shapes"][key] = list(arrays[f"a{i}"].shape)
            flat += 1
    assert flat == k                  # one code array per slot
    np.savez(d / "arrays.npz", **arrays)
    (d / "manifest.json").write_text(json.dumps(man))

    restored, _ = restore_state(str(d), state)
    restored = jax.tree.map(jnp.asarray, restored)
    for _ in range(3):
        state, restored = step(state), step(restored)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            assert a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_training_matches_leaf_training():
    """dp=1 smoke: the packed representation must not change the math —
    losses bit-match the per-leaf engine step for step."""
    from repro.configs import get_config
    from repro.data import ShardedTokenDataset
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.specs import train_input_specs
    from repro.models import reduced
    from repro.optim import sgd
    from repro.train import (Trainer, init_train_state, make_distribution,
                             make_train_step_bundle)

    cfg = dataclasses.replace(reduced(get_config("qwen3-0.6b"), d_model=64),
                              param_dtype="float32", compute_dtype="float32")
    dist = make_distribution(make_smoke_mesh(1, 1), "replica")
    opt = sgd(0.3, momentum=0.9)
    ss, sa, bs = train_input_specs(cfg, dist, 24, 4, opt)
    losses = {}
    for packed in (False, True):
        bundle = make_train_step_bundle(
            cfg, dist, opt, state_shapes=ss, state_axes=sa, batch_shapes=bs,
            protocol="gossip", remat=False, gossip_packed=packed)
        assert (bundle.layout is not None) == packed
        state, _ = init_train_state(jax.random.key(0), cfg, dist, opt,
                                    packed=packed, layout=bundle.layout)
        ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=24, n_shards=1,
                                 batch_per_shard=4, seed=0)
        losses[packed] = [h["loss"] for h in
                          Trainer(bundle, state, ds, log_every=0).run(5)]
    np.testing.assert_allclose(losses[True], losses[False],
                               rtol=2e-4, atol=2e-4)


def test_lars_packed_matches_leaf():
    """The packed-aware lars reads per-LAYER norms through the unpack view:
    its update on a PackedParams state must bit-match the per-leaf update on
    the equivalent leaf state (trust ratios never span a bucket)."""
    from repro.optim import lars
    opt = lars(0.1, momentum=0.9, weight_decay=1e-4)
    assert not opt.elementwise and opt.packed_aware
    tree = _odd_tree(jnp.float32, lead=(4,))
    grads = jax.tree.map(lambda x: x * 0.1 + 0.01, tree)
    layout = build_layout(tree, skip_leading=1)

    st_leaf = opt.init(tree)
    p_leaf, g_leaf = tree, grads
    packed = PackedParams.pack(tree, layout)
    st_packed = opt.init(packed)
    p_pack, g_pack = packed, PackedParams.pack(grads, layout)
    for _ in range(3):
        p_leaf, st_leaf = opt.update(p_leaf, g_leaf, st_leaf)
        p_pack, st_packed = opt.update(p_pack, g_pack, st_packed)
        assert isinstance(p_pack, PackedParams)
        assert isinstance(st_packed["mom"], PackedParams)
        up = p_pack.unpack()
        um = st_packed["mom"].unpack()
        for k in tree:
            np.testing.assert_array_equal(np.asarray(up[k]),
                                          np.asarray(p_leaf[k]))
            np.testing.assert_array_equal(np.asarray(um[k]),
                                          np.asarray(st_leaf["mom"][k]))


def test_lars_trains_packed_and_matches_leaf_training():
    """End to end: the make_train_step_bundle guard admits lars in packed
    mode and packed/leaf training losses coincide."""
    import dataclasses
    from repro.configs import get_config
    from repro.data import ShardedTokenDataset
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.specs import train_input_specs
    from repro.models import reduced
    from repro.optim import lars
    from repro.train import (Trainer, init_train_state, make_distribution,
                             make_train_step_bundle)

    cfg = dataclasses.replace(reduced(get_config("qwen3-0.6b"), d_model=64),
                              param_dtype="float32", compute_dtype="float32")
    dist = make_distribution(make_smoke_mesh(1, 1), "replica")
    opt = lars(0.5, momentum=0.9)
    ss, sa, bs = train_input_specs(cfg, dist, 24, 4, opt)
    losses = {}
    for packed in (False, True):
        bundle = make_train_step_bundle(
            cfg, dist, opt, state_shapes=ss, state_axes=sa, batch_shapes=bs,
            protocol="gossip", remat=False, gossip_packed=packed)
        state, _ = init_train_state(jax.random.key(0), cfg, dist, opt,
                                    packed=packed, layout=bundle.layout)
        ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=24, n_shards=1,
                                 batch_per_shard=4, seed=0)
        losses[packed] = [h["loss"] for h in
                          Trainer(bundle, state, ds, log_every=0).run(4)]
    np.testing.assert_allclose(losses[True], losses[False],
                               rtol=2e-4, atol=2e-4)


def test_packed_trainer_donates_state_buffers():
    """Packed states donate into the step (Trainer default): after the first
    step the initial state's bucket buffers — params AND optimizer moments,
    which the fused mix+apply kernel aliases in place — are consumed: the
    per-step update writes onto the previous step's buffers instead of
    double-allocating. Per-leaf states keep donation off and stay live."""
    import dataclasses
    from repro.configs import get_config
    from repro.data import ShardedTokenDataset
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.specs import train_input_specs
    from repro.models import reduced
    from repro.optim import sgd
    from repro.train import (Trainer, init_train_state, make_distribution,
                             make_train_step_bundle)

    cfg = dataclasses.replace(reduced(get_config("qwen3-0.6b"), d_model=64),
                              param_dtype="float32", compute_dtype="float32")
    dist = make_distribution(make_smoke_mesh(1, 1), "replica")
    opt = sgd(0.3, momentum=0.9)
    ss, sa, bs = train_input_specs(cfg, dist, 24, 4, opt)
    for packed in (True, False):
        for fused in ((True, False) if packed else (False,)):
            bundle = make_train_step_bundle(
                cfg, dist, opt, state_shapes=ss, state_axes=sa,
                batch_shapes=bs, protocol="gossip", remat=False,
                gossip_packed=packed, fused_update=fused)
            assert bundle.fused == fused
            state, _ = init_train_state(jax.random.key(0), cfg, dist, opt,
                                        packed=packed, layout=bundle.layout)
            initial_params = jax.tree.leaves(state["params"])
            initial_moments = jax.tree.leaves(state["opt"]["mom"])
            ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=24, n_shards=1,
                                     batch_per_shard=4, seed=0)
            tr = Trainer(bundle, state, ds, log_every=0)
            assert tr.donate == packed
            tr.run(2)
            deleted = [leaf.is_deleted() for leaf in initial_params]
            mom_deleted = [leaf.is_deleted() for leaf in initial_moments]
            if packed:
                assert all(deleted), "donated buckets must not stay live"
                # the donated optimizer-state buffers must be reused too:
                # the fused kernel writes moments in place, so the initial
                # moment buckets cannot survive the first step
                assert all(mom_deleted), \
                    "donated moment buckets must not stay live"
                live = jax.tree.leaves(
                    (tr.state["params"], tr.state["opt"]["mom"]))
                assert not any(leaf.is_deleted() for leaf in live)
            else:
                assert not any(deleted) and not any(mom_deleted)


_EQUIV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import (build_schedule, make_gossip_mix,
                        make_packed_gossip_mix, gossip_mix_sim,
                        build_layout, PackedParams)
from repro.kernels import gossip_mix_bucket

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
p = 8
sched = build_schedule(p, num_rotations=2, seed=11)
rng = np.random.default_rng(2)

for dtype, tol in ((jnp.float32, 0.0), (jnp.bfloat16, 2e-2)):
    tree = {
        "w1": jnp.asarray(rng.normal(size=(p, 5, 3)), jnp.float32).astype(dtype),
        "w2": jnp.asarray(rng.normal(size=(p, 130)), jnp.float32).astype(dtype),
        "w3": jnp.asarray(rng.normal(size=(p, 2, 7, 11)), jnp.float32).astype(dtype),
    }
    specs = {"w1": P("data", None, None), "w2": P("data", None),
             "w3": P("data", None, None, None)}
    layout = build_layout(tree, skip_leading=1)
    pmix = make_packed_gossip_mix(
        mesh, ("data",), sched, layout,
        mix_impl=lambda a, b, al: gossip_mix_bucket(a, b, al))
    lmix = make_gossip_mix(mesh, ("data",), sched, specs)
    got_p = PackedParams.pack(tree, layout)
    got_l = dict(tree); want = dict(tree)
    for t in range(sched.period):  # every phase of the p=8 schedule
        got_p = pmix(got_p, t)
        got_l = lmix(got_l, t)
        want = gossip_mix_sim(want, jnp.asarray(sched.recv_from(t)))
        up = got_p.unpack()
        for k in tree:
            a = np.asarray(up[k], np.float32)
            w = np.asarray(want[k], np.float32)
            l = np.asarray(got_l[k], np.float32)
            if tol == 0.0:  # fp32: bit-identical across both engines
                np.testing.assert_array_equal(a, w)
                np.testing.assert_array_equal(l, w)
            else:
                np.testing.assert_allclose(a, w, rtol=tol, atol=tol)
                np.testing.assert_allclose(l, w, rtol=tol, atol=tol)
    print(f"ok dtype={np.dtype(dtype).name} phases={sched.period}")

# the packed mix step must contain no per-step pack/unpack
jx = str(jax.make_jaxpr(lambda q: pmix(q, 0))(got_p))
assert "concatenate" not in jx, "packed mix has a per-step concat"
print("ok jaxpr no-concat")
print("ALL_OK")
"""


@pytest.mark.slow
def test_bucketed_equals_leaf_all_phases():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", _EQUIV_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL_OK" in r.stdout
