"""Bounded-delay async gossip runtime (staleness-k inbox ring, GossipGraD
§4.2/§5).

Covers: the delayed-k oracle algebra (bootstrap skips, k=1 equivalence with
the PR-2 staleness-1 oracle, row-stochasticity under drops, mean
preservation without drops); the shard_map implementations == the oracle
bit-exactly at p=8 (fp32, every schedule phase, per-leaf + packed, static +
dynamic, k in {1,2,4}, with and without injected drops); bounded replica
drift vs sync gossip across staleness and drop rate; protocol/state
plumbing at dp=1 (degenerates to local SGD exactly); ring checkpoint
roundtrips including cross-staleness mask-padding/truncation and the legacy
bare-inbox format; the trainer's in-flight window bounding at 2 + 2*k; and
(subprocess, 8 forced host devices) end-to-end train + save + restore +
continue determinism through the real bundle/trainer/checkpoint stack at
k in {1, 2}, drops included.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PROTOCOLS, build_schedule, exchange_ok,
                        gossip_mix_sim_delayed, gossip_mix_sim_delayed_k,
                        init_inbox_ring, make_async_sim_train_step,
                        make_sim_train_step, replicate)
from repro.optim import sgd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ oracle algebra

def test_ring_bootstrap_skips_first_k_mixes():
    """The all-invalid bootstrap makes the first k arrival mixes identity
    (nothing received yet), and the slot dispatched at step 0 is consumed —
    valid — at step k."""
    p, k = 8, 3
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(p, 5)), jnp.float32)}
    ring = init_inbox_ring(params, k, p)
    sched = build_schedule(p, seed=1)
    cur = params
    for t in range(k):
        assert not np.asarray(ring["valid"])[:, 0].any()
        mixed, ring = gossip_mix_sim_delayed_k(
            cur, ring, jnp.asarray(sched.recv_from(t)))
        np.testing.assert_array_equal(np.asarray(mixed["w"]),
                                      np.asarray(cur["w"]))
        cur = mixed
    # step k consumes the step-0 dispatch: valid, and equal to the step-0
    # mixed params gathered through schedule row 0
    assert np.asarray(ring["valid"])[:, 0].all()
    np.testing.assert_array_equal(
        np.asarray(ring["slots"][0]["w"]),
        np.asarray(params["w"])[np.asarray(sched.recv_from(0))])
    assert int(ring["t"]) == k


def test_delayed_k1_matches_staleness1_oracle():
    """k=1 with zero drops reproduces the PR-2 staleness-1 oracle bit-for-
    bit (params and in-flight payload both) — the refactor changes the
    carry structure, not the numbers."""
    p = 8
    sched = build_schedule(p, num_rotations=3, seed=4)
    rng = np.random.default_rng(2)
    params_new = {"a": jnp.asarray(rng.normal(size=(p, 3, 2)), jnp.float32)}
    params_old = dict(params_new)
    ring = init_inbox_ring(params_new, 1, p)
    inbox = jax.tree.map(jnp.copy, params_old)
    for t in range(2 * sched.period):
        recv = jnp.asarray(sched.recv_from(t))
        params_new, ring = gossip_mix_sim_delayed_k(params_new, ring, recv)
        params_old, inbox = gossip_mix_sim_delayed(params_old, inbox, recv)
        np.testing.assert_array_equal(np.asarray(params_new["a"]),
                                      np.asarray(params_old["a"]))
        np.testing.assert_array_equal(np.asarray(ring["slots"][0]["a"]),
                                      np.asarray(inbox["a"]))


@pytest.mark.parametrize("k", [1, 2, 4])
def test_delayed_k_preserves_replica_mean(k):
    """With no drops, each arrival mix is (1-a)I + a*P after the bootstrap —
    column sums are 1, so the replica mean is invariant step to step."""
    p = 8
    sched = build_schedule(p, num_rotations=3, seed=4)
    rng = np.random.default_rng(2)
    params = {"a": jnp.asarray(rng.normal(size=(p, 3, 2)), jnp.float32)}
    ring = init_inbox_ring(params, k, p)
    mean0 = np.asarray(params["a"]).mean(0)
    for t in range(2 * sched.period + k):
        params, ring = gossip_mix_sim_delayed_k(
            params, ring, jnp.asarray(sched.recv_from(t)))
    np.testing.assert_allclose(np.asarray(params["a"]).mean(0), mean0,
                               rtol=1e-5, atol=1e-6)


def test_delayed_k_row_stochastic_under_drops():
    """Skip-on-timeout keeps every mixing-matrix row summing to 1: a
    consensus state (all replicas equal) is a fixed point under ANY drop
    pattern — a dropped exchange degenerates to the identity row, it never
    rescales the local model."""
    p, k = 8, 2
    sched = build_schedule(p, seed=7)
    const = jnp.full((p, 4), 3.25, jnp.float32)
    params = {"w": const}
    ring = init_inbox_ring(params, k, p)
    rng = np.random.default_rng(0)
    for t in range(3 * sched.period):
        ok = jnp.asarray(rng.integers(0, 2, size=(p,)), jnp.float32)
        params, ring = gossip_mix_sim_delayed_k(
            params, ring, jnp.asarray(sched.recv_from(t)), 0.5, ok)
        np.testing.assert_array_equal(np.asarray(params["w"]),
                                      np.asarray(const))


def test_exchange_ok_deterministic_and_rate():
    """The drop-injection hash is deterministic (same (t, rank, seed) ->
    same bit, vectorized == per-rank) and hits the requested marginal rate."""
    ranks = jnp.arange(64)
    a = exchange_ok(5, ranks, seed=3, rate=0.3)
    b = exchange_ok(5, ranks, seed=3, rate=0.3)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    per_rank = jnp.stack([exchange_ok(5, r, seed=3, rate=0.3)
                          for r in range(64)])
    np.testing.assert_array_equal(np.asarray(a), np.asarray(per_rank))
    assert set(np.unique(np.asarray(a))) <= {0.0, 1.0}
    # marginal rate over many (t, rank) draws
    big = np.mean([np.asarray(exchange_ok(t, ranks, seed=1, rate=0.3))
                   for t in range(64)])
    assert 0.6 < big < 0.8, big  # ~70% land at rate 0.3
    np.testing.assert_array_equal(
        np.asarray(exchange_ok(5, ranks, seed=3, rate=0.0)), 1.0)


# --------------------------------------------------- convergence equivalence

def _quadratic_loss(target):
    def loss(params, batch):
        return jnp.sum((params["w"] - target - batch) ** 2)
    return loss


def _run_sim(protocol, p=8, steps=None, lr=0.05, seed=3, shard_bias=1.0,
             num_rotations=2, staleness=1, drop_rate=0.0):
    sched = build_schedule(p, num_rotations=num_rotations, seed=seed)
    steps = steps if steps is not None else 4 * sched.period
    target = jnp.arange(4.0)
    loss = _quadratic_loss(target)
    opt = sgd(lr, momentum=0.0)
    params = replicate({"w": jnp.zeros(4)}, p)
    opt_state = opt.init(params)
    rng = np.random.default_rng(seed)
    bias = rng.normal(scale=shard_bias, size=(p, 4)) if shard_bias else 0.0
    hist = []
    if protocol == "gossip_async":
        step = make_async_sim_train_step(loss, opt, sched,
                                         staleness=staleness,
                                         drop_rate=drop_rate, drop_seed=seed)
        ring = init_inbox_ring(params, staleness, p)
        for t in range(steps):
            batch = jnp.asarray(bias + rng.normal(scale=0.1, size=(p, 4)),
                                jnp.float32)
            opt_state, params, ring, m = step(opt_state, params, ring,
                                              batch, jnp.int32(t))
            hist.append({k: float(v) for k, v in m.items()})
    else:
        step = make_sim_train_step(loss, opt, sched, protocol=protocol)
        for t in range(steps):
            batch = jnp.asarray(bias + rng.normal(scale=0.1, size=(p, 4)),
                                jnp.float32)
            opt_state, params, m = step(opt_state, params, batch,
                                        jnp.int32(t))
            hist.append({k: float(v) for k, v in m.items()})
    return params, hist, target, sched


def test_async_reaches_optimum_and_consensus():
    params, hist, target, _ = _run_sim("gossip_async", steps=120,
                                       shard_bias=0.0)
    w = np.asarray(params["w"])
    assert np.allclose(w, np.asarray(target)[None], atol=0.15)
    assert hist[-1]["replica_variance"] < 1e-3


def test_async_drift_within_2x_of_sync():
    """Acceptance: replica drift under gossip_async stays within 2x of sync
    gossip over >= 2 full rotation periods (here 4, averaged over the last
    period to damp step noise) — at every supported staleness."""
    for seed in (3, 5):
        _, h_sync, _, sched = _run_sim("gossip", seed=seed)
        tail = sched.period
        drift_sync = np.mean([h["replica_variance"] for h in h_sync[-tail:]])
        for k in (1, 2, 4):
            _, h_async, _, _ = _run_sim("gossip_async", seed=seed,
                                        staleness=k)
            assert len(h_async) >= 2 * sched.period
            drift_async = np.mean([h["replica_variance"]
                                   for h in h_async[-tail:]])
            assert drift_async <= 2.0 * drift_sync, (
                seed, k, drift_async, drift_sync)


def test_async_drift_bounded_under_drops():
    """Skip-on-timeout degrades drift gracefully: 30% injected drops on a
    staleness-4 ring keeps replica variance within an order of magnitude of
    sync gossip (measured ~4x; bound 6x for seed robustness) and the loss
    still converges to the same neighborhood."""
    for seed in (3, 5):
        _, h_sync, _, sched = _run_sim("gossip", seed=seed)
        tail = sched.period
        drift_sync = np.mean([h["replica_variance"] for h in h_sync[-tail:]])
        _, h_drop, _, _ = _run_sim("gossip_async", seed=seed, staleness=4,
                                   drop_rate=0.3)
        drift_drop = np.mean([h["replica_variance"] for h in h_drop[-tail:]])
        assert drift_drop <= 6.0 * drift_sync, (seed, drift_drop, drift_sync)


def test_async_tracks_sync_gossip_loss():
    """Convergence equivalence: staleness-1 matches sync gossip's final loss
    within noise (the paper's §5/§6 claim)."""
    _, h_async, _, _ = _run_sim("gossip_async", steps=120, shard_bias=0.0)
    _, h_sync, _, _ = _run_sim("gossip", steps=120, shard_bias=0.0)
    assert abs(h_async[-1]["loss"] - h_sync[-1]["loss"]) < 0.1


# ------------------------------------------------------------- protocol API

def test_protocol_registry_and_staleness_contract():
    from repro.core import make_protocol
    from repro.launch.mesh import make_smoke_mesh
    assert "gossip_async" in PROTOCOLS
    mesh = make_smoke_mesh(1, 1)
    proto = make_protocol("gossip_async", mesh, ("data",), {}, staleness=4)
    # dp=1 degenerates to local SGD: no ring, passthrough comm_params —
    # staleness is 0 regardless of the requested ring depth
    assert proto.staleness == 0 and not proto.carries_inbox
    tree = {"w": jnp.ones((1, 3))}
    out = proto.comm_params(tree, 0)
    assert out is tree
    with pytest.raises(ValueError, match="staleness"):
        make_protocol("gossip_async", mesh, ("data",), {}, staleness=0)


def test_dp1_async_trainer_bitmatches_sync(tiny_bundle_factory):
    """At dp=1 gossip_async must be exactly local SGD — bitwise the same
    losses as sync gossip (both protocols degenerate), at any requested
    staleness."""
    losses = {}
    losses["gossip"] = tiny_bundle_factory("gossip", packed=True, steps=4)
    for k in (1, 4):
        losses[k] = tiny_bundle_factory("gossip_async", packed=True, steps=4,
                                        staleness=k)
        np.testing.assert_array_equal(losses["gossip"], losses[k])


@pytest.fixture
def tiny_bundle_factory():
    import dataclasses
    from repro.configs import get_config
    from repro.data import ShardedTokenDataset
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.specs import train_input_specs
    from repro.models import reduced
    from repro.train import (Trainer, init_train_state, make_distribution,
                             make_train_step_bundle)

    def run(protocol, packed=False, steps=4, staleness=1):
        cfg = dataclasses.replace(
            reduced(get_config("qwen3-0.6b"), d_model=64),
            param_dtype="float32", compute_dtype="float32")
        dist = make_distribution(make_smoke_mesh(1, 1), "replica")
        opt = sgd(0.3, momentum=0.9)
        ss, sa, bs = train_input_specs(cfg, dist, 24, 4, opt)
        bundle = make_train_step_bundle(
            cfg, dist, opt, state_shapes=ss, state_axes=sa, batch_shapes=bs,
            protocol=protocol, remat=False, gossip_packed=packed,
            staleness=staleness)
        state, _ = init_train_state(
            jax.random.key(0), cfg, dist, opt, packed=packed,
            layout=bundle.layout, inbox=bundle.protocol.staleness)
        ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=24, n_shards=1,
                                 batch_per_shard=4, seed=0)
        return [h["loss"] for h in
                Trainer(bundle, state, ds, log_every=0).run(steps)]

    return run


# --------------------------------------------------- trainer in-flight window

def test_trainer_inflight_window_bounds():
    """The dispatch window is sized 2 + 2*staleness and actually bounds the
    number of dispatched-but-unfinished steps: after every step the in-
    flight deque holds at most the window, and with enough steps it
    saturates exactly at it."""
    import types
    from repro.data import ShardedTokenDataset
    from repro.train import Trainer

    class _Dist:
        dp = 1

    for k in (0, 1, 3):
        proto = types.SimpleNamespace(staleness=k, period=1)
        step_fn = lambda state, batch: (state, batch,
                                        {"loss": jnp.float32(0.0)})
        bundle = types.SimpleNamespace(
            protocol=proto, dist=_Dist(), layout=None,
            batch_shardings=None,   # batches on the default device
            jitted=lambda phase, donate=True: step_fn)
        ds = ShardedTokenDataset(vocab=32, seq_len=8, n_shards=1,
                                 batch_per_shard=1, seed=0)
        tr = Trainer(bundle, {"params": jnp.zeros(3)}, ds, log_every=0)
        window = 2 + 2 * k
        assert tr.inflight_window == window
        seen = []
        orig = tr._bound_inflight
        def record(metrics, _orig=orig, _seen=seen, _tr=tr):
            _orig(metrics)
            _seen.append(len(_tr._inflight))
        tr._bound_inflight = record
        tr.run(3 * window)
        assert max(seen) == window, (k, max(seen))
        assert all(s <= window for s in seen)


# ------------------------------------------------------- ring checkpointing

def _ring_state(k, dp=4, seed=7, step=9):
    from repro.core.buckets import PackedParams
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    tree = {"w1": mk(dp, 5, 3), "w2": mk(dp, 130)}
    packed = PackedParams.pack(tree, skip_leading=1)
    ring = {
        "slots": tuple(
            PackedParams.pack(jax.tree.map(lambda x, _i=i: x + 1.0 + _i,
                                           tree), skip_leading=1)
            for i in range(k)),
        "valid": jnp.asarray(rng.integers(0, 2, size=(dp, k)), jnp.float32),
        "t": jnp.asarray(step, jnp.int32),
    }
    return {"params": packed, "opt": {"step": jnp.int32(step)},
            "inbox": ring}, tree


def test_ring_checkpoint_roundtrip(tmp_path):
    """The staleness-k ring (PackedParams slots, validity mask, dispatch
    counter) persists through the leaf-keyed checkpoint format and restores
    bit-exactly."""
    from repro.checkpoint import (checkpoint_exists, read_manifest,
                                  restore_state, save_state)
    from repro.core.buckets import PackedParams
    state, tree = _ring_state(k=3)
    d = str(tmp_path / "ck")
    assert not checkpoint_exists(d)
    save_state(d, state, step=9, metadata={"protocol": "gossip_async",
                                           "staleness": 3})
    assert checkpoint_exists(d)
    man = read_manifest(d)
    assert man["step"] == 9 and man["metadata"]["staleness"] == 3
    rest, _ = restore_state(d, state)
    assert len(rest["inbox"]["slots"]) == 3
    np.testing.assert_array_equal(np.asarray(rest["inbox"]["valid"]),
                                  np.asarray(state["inbox"]["valid"]))
    assert int(rest["inbox"]["t"]) == 9
    for i in range(3):
        assert isinstance(rest["inbox"]["slots"][i], PackedParams)
        got = rest["inbox"]["slots"][i].unpack()
        want = state["inbox"]["slots"][i].unpack()
        for k_ in tree:
            np.testing.assert_array_equal(np.asarray(got[k_]),
                                          np.asarray(want[k_]))
    # params and ring slots restore as DISTINCT values (no buffer aliasing)
    np.testing.assert_array_equal(np.asarray(rest["params"].unpack()["w1"]),
                                  np.asarray(tree["w1"]))


def test_ring_checkpoint_cross_staleness(tmp_path):
    """A k=1 checkpoint restores into a k=4 template by mask-padding (the
    in-flight payload stays oldest, new back slots invalid) and a k=4
    checkpoint truncates into a k=1 template (newest in-flight payloads
    dropped — 'lost on the wire', tolerated by design)."""
    from repro.checkpoint import restore_state, save_state
    state1, _ = _ring_state(k=1, step=5)
    d1 = str(tmp_path / "ck1")
    save_state(d1, state1, step=5, metadata={"staleness": 1})
    template4, _ = _ring_state(k=4, seed=13, step=0)
    rest4, _ = restore_state(d1, template4)
    assert len(rest4["inbox"]["slots"]) == 4
    np.testing.assert_array_equal(
        np.asarray(rest4["inbox"]["slots"][0].unpack()["w1"]),
        np.asarray(state1["inbox"]["slots"][0].unpack()["w1"]))
    v = np.asarray(rest4["inbox"]["valid"])
    np.testing.assert_array_equal(v[:, 0],
                                  np.asarray(state1["inbox"]["valid"])[:, 0])
    assert not v[:, 1:].any()
    assert int(rest4["inbox"]["t"]) == 5

    # ...and back: k=4 -> k=1 keeps the OLDEST slot
    state4, _ = _ring_state(k=4, step=11)
    d4 = str(tmp_path / "ck4")
    save_state(d4, state4, step=11, metadata={"staleness": 4})
    template1, _ = _ring_state(k=1, seed=17, step=0)
    rest1, _ = restore_state(d4, template1)
    assert len(rest1["inbox"]["slots"]) == 1
    np.testing.assert_array_equal(
        np.asarray(rest1["inbox"]["slots"][0].unpack()["w2"]),
        np.asarray(state4["inbox"]["slots"][0].unpack()["w2"]))
    np.testing.assert_array_equal(np.asarray(rest1["inbox"]["valid"]),
                                  np.asarray(state4["inbox"]["valid"])[:, :1])


def test_legacy_inbox_checkpoint_restores_as_ring(tmp_path):
    """A PR-2 checkpoint (bare staleness-1 inbox tree, no ring keys)
    restores into a ring template: one valid slot, dispatch counter resumed
    from the manifest step."""
    from repro.checkpoint import restore_state, save_state
    from repro.core.buckets import PackedParams
    rng = np.random.default_rng(7)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    tree = {"w1": mk(4, 5, 3), "w2": mk(4, 130)}
    inbox_tree = jax.tree.map(lambda x: x + 1.0, tree)
    legacy = {"params": PackedParams.pack(tree, skip_leading=1),
              "opt": {"step": jnp.int32(9)},
              "inbox": PackedParams.pack(inbox_tree, skip_leading=1)}
    d = str(tmp_path / "ck")
    save_state(d, legacy, step=9, metadata={"protocol": "gossip_async"})
    template, _ = _ring_state(k=2, seed=13, step=0)
    rest, _ = restore_state(d, template)
    assert len(rest["inbox"]["slots"]) == 2
    got = rest["inbox"]["slots"][0].unpack()
    for k_ in tree:
        np.testing.assert_array_equal(np.asarray(got[k_]),
                                      np.asarray(inbox_tree[k_]))
    v = np.asarray(rest["inbox"]["valid"])
    assert v[:, 0].all() and not v[:, 1:].any()
    assert int(rest["inbox"]["t"]) == 9


# ------------------------ p=8 subprocess: oracle equivalence + e2e determinism

_EQUIV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import (build_schedule, build_layout, PackedParams,
                        exchange_ok, init_inbox_ring, make_async_gossip_mix,
                        make_packed_async_gossip_mix, gossip_mix_sim_delayed,
                        gossip_mix_sim_delayed_k)
from repro.kernels import gossip_mix_bucket

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
p = 8
sched = build_schedule(p, num_rotations=2, seed=11)
rng = np.random.default_rng(2)
tree = {
    "w1": jnp.asarray(rng.normal(size=(p, 5, 3)), jnp.float32),
    "w2": jnp.asarray(rng.normal(size=(p, 130)), jnp.float32),
    "w3": jnp.asarray(rng.normal(size=(p, 2, 7, 11)), jnp.float32),
}
specs = {"w1": P("data", None, None), "w2": P("data", None),
         "w3": P("data", None, None, None)}
layout = build_layout(tree, skip_leading=1)

def ring_check(ring, want):
    np.testing.assert_array_equal(np.asarray(ring["valid"]),
                                  np.asarray(want["valid"]))
    assert int(ring["t"]) == int(want["t"])

CASES = [(k, rate, "static") for k in (1, 2, 4) for rate in (0.0, 0.35)]
CASES += [(2, 0.0, "dynamic"), (2, 0.35, "dynamic")]
for k, rate, mode in CASES:
    lmix = make_async_gossip_mix(mesh, ("data",), sched, specs, mode=mode,
                                 staleness=k, drop_rate=rate, drop_seed=3)
    pmix = make_packed_async_gossip_mix(
        mesh, ("data",), sched, layout, mode=mode, staleness=k,
        drop_rate=rate, drop_seed=3,
        mix_impl=lambda a, b, al: gossip_mix_bucket(a, b, al))
    got_l = dict(tree); ring_l = init_inbox_ring(got_l, k, p)
    got_p = PackedParams.pack(tree, layout)
    ring_p = init_inbox_ring(got_p, k, p)
    want = dict(tree); ring_w = init_inbox_ring(want, k, p)
    for t in range(sched.period + k + 1):  # every phase + wraparound
        ph = t if mode == "static" else jnp.int32(t)
        got_l, ring_l = lmix(got_l, ring_l, ph)
        got_p, ring_p = pmix(got_p, ring_p, ph)
        ok = exchange_ok(ring_w["t"], jnp.arange(p), 3, rate)
        want, ring_w = gossip_mix_sim_delayed_k(
            want, ring_w, jnp.asarray(sched.recv_from(t)), 0.5, ok)
        ring_check(ring_l, ring_w); ring_check(ring_p, ring_w)
        up = got_p.unpack()
        for kk in tree:  # fp32: bit-identical, params AND every ring slot
            np.testing.assert_array_equal(np.asarray(got_l[kk]),
                                          np.asarray(want[kk]))
            np.testing.assert_array_equal(np.asarray(up[kk]),
                                          np.asarray(want[kk]))
        for sl, sp, sw in zip(ring_l["slots"], ring_p["slots"],
                              ring_w["slots"]):
            spu = sp.unpack()
            for kk in tree:
                np.testing.assert_array_equal(np.asarray(sl[kk]),
                                              np.asarray(sw[kk]))
                np.testing.assert_array_equal(np.asarray(spu[kk]),
                                              np.asarray(sw[kk]))
    print(f"ok k={k} rate={rate} mode={mode}")

# k=1 zero drops == the PR-2 staleness-1 oracle, trajectory-for-trajectory
want = dict(tree); ring = init_inbox_ring(want, 1, p)
old = dict(tree); old_inbox = jax.tree.map(jnp.copy, old)
for t in range(sched.period + 2):
    recv = jnp.asarray(sched.recv_from(t))
    want, ring = gossip_mix_sim_delayed_k(want, ring, recv)
    old, old_inbox = gossip_mix_sim_delayed(old, old_inbox, recv)
    for kk in tree:
        np.testing.assert_array_equal(np.asarray(want[kk]),
                                      np.asarray(old[kk]))
        np.testing.assert_array_equal(np.asarray(ring["slots"][0][kk]),
                                      np.asarray(old_inbox[kk]))
print("ok k=1 pr2-oracle parity")

# the packed async mix step must contain no per-step bucket pack/unpack:
# the only concatenate allowed is the (dp, k) validity-mask roll
def collect(jaxpr, out):
    for eqn in jaxpr.eqns:
        sizes = [int(np.prod(v.aval.shape)) for v in eqn.outvars
                 if hasattr(v.aval, "shape")]
        out.append((eqn.primitive.name, max(sizes) if sizes else 0))
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(x, "eqns"):
                    collect(x, out)
                elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                    collect(x.jaxpr, out)

jx = jax.make_jaxpr(lambda q, b: pmix(q, b, 0))(got_p, ring_p)
eqns = []
collect(jx.jaxpr, eqns)
min_bucket = min(layout.bucket_sizes)
cats = [(n, s) for n, s in eqns if n == "concatenate" and s >= min_bucket]
assert not cats, f"packed async mix has a per-step bucket concat: {cats}"
print("ok jaxpr no-bucket-concat")
print("ALL_OK")
"""


@pytest.mark.slow
def test_async_shardmap_matches_delayed_k_oracle():
    """Acceptance: staleness-k shard_map implementation == simulator oracle
    bit-exactly (fp32, p=8) across all schedule phases — per-leaf and
    packed, static and dynamic phase selection, k in {1,2,4}, with and
    without injected drops, params + every ring slot + validity mask; k=1
    with zero drops reproduces the PR-2 staleness-1 oracle exactly."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", _EQUIV_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL_OK" in r.stdout


_E2E_SCRIPT = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import restore_state, save_state
from repro.configs import get_config
from repro.data import ShardedTokenDataset
from repro.launch.mesh import make_smoke_mesh
from repro.launch.specs import train_input_specs
from repro.models import reduced
from repro.optim import sgd
from repro.train import (Trainer, init_train_state, make_distribution,
                         make_train_step_bundle)

cfg = dataclasses.replace(reduced(get_config("qwen3-0.6b"), d_model=32),
                          param_dtype="float32", compute_dtype="float32")
dist = make_distribution(make_smoke_mesh(8, 1), "replica")
assert dist.dp == 8
opt = sgd(0.3, momentum=0.9)
ss, sa, bs = train_input_specs(cfg, dist, 16, 16, opt)

def make(k, drop=0.0, n_seed=0):
    bundle = make_train_step_bundle(
        cfg, dist, opt, state_shapes=ss, state_axes=sa, batch_shapes=bs,
        protocol="gossip_async", remat=False, gossip_packed=True,
        staleness=k, drop_rate=drop)
    assert bundle.protocol.staleness == k
    state, _ = init_train_state(jax.random.key(n_seed), cfg, dist, opt,
                                packed=True, layout=bundle.layout,
                                inbox=bundle.protocol.staleness)
    assert len(state["inbox"]["slots"]) == k
    ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=16, n_shards=8,
                             batch_per_shard=2, seed=0)
    return bundle, state, ds

for K, DROP in ((1, 0.0), (2, 0.2)):
    # straight run: 2N steps
    bundle, state, ds = make(K, DROP)
    tr = Trainer(bundle, state, ds, log_every=0)
    assert tr.inflight_window == 2 + 2 * K
    hist_straight = tr.run(8)

    # resumed run: N steps, checkpoint (ring + step), restore, N more
    bundle, state, ds = make(K, DROP)
    tr1 = Trainer(bundle, state, ds, log_every=0)
    tr1.run(4)
    ckdir = tempfile.mkdtemp()
    save_state(ckdir, tr1.state, step=4,
               metadata={"protocol": "gossip_async", "staleness": K})
    bundle2, state2, ds2 = make(K, DROP, n_seed=1)  # different init
    restored, man = restore_state(ckdir, state2)
    tr2 = Trainer(bundle2, restored, ds2, log_every=0)
    hist_resumed = tr2.run(4, start_step=man["step"])

    a = [h["loss"] for h in hist_straight[4:]]
    b = [h["loss"] for h in hist_resumed]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the resumed state (params AND every ring slot) bit-matches
    for k_ in ("params", "inbox"):
        for x, y in zip(jax.tree.leaves(tr.state[k_]),
                        jax.tree.leaves(tr2.state[k_])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    print(f"ok e2e k={K} drop={DROP}")

# cross-staleness restore through the real stack: the k=1 checkpoint above
# (from the K loop's first pass) boots a k=4 run via mask-padding
bundle, state, ds = make(1)
tr = Trainer(bundle, state, ds, log_every=0)
tr.run(4)
ckdir = tempfile.mkdtemp()
save_state(ckdir, tr.state, step=4,
           metadata={"protocol": "gossip_async", "staleness": 1})
b4, s4, ds4 = make(4, n_seed=2)
r4, man = restore_state(ckdir, s4)
v = np.asarray(r4["inbox"]["valid"])
assert v.shape == (8, 4) and v[:, 0].all() and not v[:, 1:].any()
tr4 = Trainer(b4, r4, ds4, log_every=0)
h4 = tr4.run(4, start_step=4)
assert all(np.isfinite(h["loss"]) for h in h4)
print("ok cross-staleness restore k1->k4")
print("E2E_OK")
"""


@pytest.mark.slow
def test_async_train_checkpoint_resume_p8():
    """Acceptance: gossip_async trains end to end at p=8 through the packed
    bundle/trainer stack at k in {1, 2} (drops included at k=2) and
    checkpoint-resume is bit-deterministic (ring slots + mask + phase
    persist); a k=1 checkpoint boots a k=4 run by mask-padding."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", _E2E_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "E2E_OK" in r.stdout
