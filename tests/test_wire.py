"""Compressed + partition-sampled gossip wire (int8/fp8 buckets, rotating
bucket subsets).

Covers: the quantize primitives (splitmix32 key/noise determinism, unbiased
stochastic int8 rounding, fp8-e4m3 clamp — no nan on overflow, bf16
downcast, the shard-local ``base_index`` global-noise contract, payload
plumbing + byte accounting); the rotating bucket-subset schedule (full
coverage per period, traced ``mask`` == host ``selected`` including
negative steps); degeneracy of the quantized oracles to the PR-1/PR-4
oracles at the default wire; sim-level drift/final-loss acceptance
(quantized + sampled wires within 2x of the uncompressed wire); protocol
plumbing at dp=1 (wire knobs are inert — bit-identical losses); wire-ring
checkpoint roundtrips (int8 codes saved natively, fp8 staged losslessly)
and the cross-wire-format ring reset; and (subprocess, 8 forced host
devices) all four wired packed engines == the ``gossip_mix_sim_quantized*``
oracles bit-exactly — int8/fp8/bf16 x full/sampled subsets, sync + async
(k in {1,2,4}, drops on/off), static + dynamic, the Pallas in-sweep decode
kernel, the fsdp shard-local layout — plus end-to-end train + checkpoint +
resume determinism and the fp32-wire PR-5 parity through the real
bundle/trainer stack.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (build_layout, build_schedule, build_subset_schedule,
                        init_inbox_ring, init_wire_inbox_ring,
                        gossip_mix_sim_delayed_k, gossip_mix_sim_quantized,
                        gossip_mix_sim_quantized_k, make_async_sim_train_step,
                        replicate, wire_bytes_per_step, wire_period,
                        wire_subset_of)
from repro.core.buckets import PackedParams
from repro.core.topology import BucketSubsetSchedule
from repro.kernels.quantize import (LANE, WIRE_DTYPES, WireFormat,
                                    decode_wire, dequant_tiles, encode_wire,
                                    payload_spec, wire_itemsize, wire_key,
                                    wire_uniform, zero_payload_like)
from repro.optim import sgd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bits_eq(a, b, msg=""):
    """Bitwise equality for any dtype (fp8/bf16 compare as raw bytes)."""
    a, b = np.atleast_1d(np.asarray(a)), np.atleast_1d(np.asarray(b))
    assert a.dtype == b.dtype and a.shape == b.shape, (a.dtype, b.dtype, msg)
    np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8),
                                  err_msg=msg)


# ------------------------------------------------------- quantize primitives

def test_wire_key_and_uniform_deterministic():
    """The stochastic-rounding stream is a pure hash: same (t, rank, bucket,
    seed) -> same bits, any key component changes the stream, and the
    vectorized rank form equals the per-rank scalars."""
    k1 = wire_key(5, 3, 2, seed=7)
    bits_eq(k1, wire_key(5, 3, 2, seed=7))
    for other in (wire_key(6, 3, 2, 7), wire_key(5, 4, 2, 7),
                  wire_key(5, 3, 1, 7), wire_key(5, 3, 2, 8)):
        assert int(k1) != int(other)
    vec = wire_key(5, jnp.arange(8), 2, seed=7)
    per = jnp.stack([wire_key(5, r, 2, seed=7) for r in range(8)])
    bits_eq(vec, per)
    u = wire_uniform(vec, 256)
    bits_eq(u, wire_uniform(vec, 256))
    un = np.asarray(u)
    assert un.shape == (8, 256)
    assert (un >= 0.0).all() and (un < 1.0).all()
    # 24-bit grid: every draw is a multiple of 2^-24
    assert np.all(un * (1 << 24) == np.round(un * (1 << 24)))


def test_wire_uniform_base_index_is_global_position():
    """``base_index`` keys noise by the GLOBAL element index: a shard's
    stream is the matching slice of the full-bucket stream (the fsdp
    shard-local noise contract)."""
    keys = wire_key(3, jnp.arange(4), 0, seed=1)
    full = wire_uniform(keys, 384)
    shard = wire_uniform(keys, 128, base_index=128)
    bits_eq(shard, np.asarray(full)[:, 128:256])
    # traced base_index (the engines derive it from axis_index) agrees
    bits_eq(wire_uniform(keys, 128, base_index=jnp.int32(128)), shard)


def test_wireformat_validation_and_flags():
    with pytest.raises(ValueError, match="wire dtype"):
        WireFormat(dtype="int4")
    with pytest.raises(ValueError, match="subset fraction"):
        WireFormat(subset=0.0)
    with pytest.raises(ValueError, match="subset fraction"):
        WireFormat(subset=1.5)
    assert WireFormat().is_default and not WireFormat().quantized
    assert not WireFormat(dtype="int8").is_default
    assert not WireFormat(subset=0.5).is_default
    assert WireFormat(dtype="fp8").quantized
    assert not WireFormat(dtype="bf16").quantized
    assert WIRE_DTYPES == ("fp32", "bf16", "int8", "fp8")


def test_int8_roundtrip_bounded_and_unbiased():
    """int8 encode: codes bounded, per-tile error < 1 scale step, and the
    stochastic rounding is unbiased — averaging the decode over many
    dispatch steps converges on the input."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 2, LANE)), jnp.float32) * 3.0
    pay = encode_wire(x, "int8", keys=wire_key(0, jnp.arange(4), 0, 0))
    assert pay["q"].shape == (4, 2, LANE) and pay["q"].dtype == jnp.int8
    assert pay["s"].shape == (4, 2) and pay["s"].dtype == jnp.float32
    dec = np.asarray(decode_wire(pay))
    step = np.repeat(np.asarray(pay["s"])[..., None], LANE, axis=-1)
    assert np.all(np.abs(dec - np.asarray(x)) <= step + 1e-7)
    acc = np.zeros_like(dec)
    n_draws = 200
    for t in range(n_draws):
        acc += np.asarray(decode_wire(encode_wire(
            x, "int8", keys=wire_key(t, jnp.arange(4), 0, 0))))
    err = np.abs(acc / n_draws - np.asarray(x))
    assert err.max() < 3.0 * step.max() / np.sqrt(n_draws), err.max()


def test_fp8_encode_finite_and_bounded():
    """fp8-e4m3 encode clamps before the cast (e4m3fn has no inf — an
    overflow would round to nan) and lands within the format's ~6%
    relative-error envelope per tile."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 1, LANE)), jnp.float32) * 1e4
    x = x.at[0, 0, 0].set(3e4)  # the tile amax itself
    pay = encode_wire(x, "fp8")
    assert pay["q"].dtype == jnp.float8_e4m3fn
    dec = np.asarray(decode_wire(pay))
    assert np.isfinite(dec).all()
    denom = np.maximum(np.abs(np.asarray(x)), 1e-30)
    scale = np.repeat(np.asarray(pay["s"])[..., None], LANE, axis=-1)
    assert np.all(np.abs(dec - np.asarray(x)) <= 0.07 * denom + scale)
    # all-zero tiles encode scale 0 and decode to exact zeros
    z = encode_wire(jnp.zeros((1, 1, LANE)), "fp8")
    assert np.asarray(z["s"])[0, 0] == 0.0
    np.testing.assert_array_equal(np.asarray(decode_wire(z)), 0.0)


def test_bf16_wire_is_plain_downcast():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(3, 128)), jnp.float32)
    bits_eq(encode_wire(x, "bf16"), x.astype(jnp.bfloat16))
    bits_eq(encode_wire(x, "fp32"), x)
    with pytest.raises(ValueError, match="wire dtype"):
        encode_wire(x, "int4")
    with pytest.raises(ValueError, match="stochastic"):
        encode_wire(x, "int8")  # keys required
    with pytest.raises(ValueError, match="lane-multiple"):
        encode_wire(jnp.zeros((2, 130)), "int8", keys=wire_key(0, 0, 0))


def test_shard_local_encode_matches_global():
    """Encoding two half-bucket shards with their global ``base_index``
    offsets reproduces the full-bucket encode bit-for-bit (amax tiles never
    straddle shards — strides are LANE multiples)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 2, LANE)), jnp.float32)
    keys = wire_key(9, jnp.arange(4), 1, seed=2)
    full = encode_wire(x, "int8", keys=keys)
    lo = encode_wire(x[:, :1], "int8", keys=keys, base_index=0)
    hi = encode_wire(x[:, 1:], "int8", keys=keys, base_index=128)
    bits_eq(np.concatenate([np.asarray(lo["q"]), np.asarray(hi["q"])], 1),
            full["q"])
    bits_eq(np.concatenate([np.asarray(lo["s"]), np.asarray(hi["s"])], 1),
            full["s"])


def test_payload_plumbing_and_itemsize():
    b = jnp.ones((2, 2, LANE), jnp.float32)
    for dt in ("int8", "fp8"):
        z = zero_payload_like(b, dt)
        assert z["q"].shape == (2, 2, LANE) and z["s"].shape == (2, 2)
        np.testing.assert_array_equal(np.asarray(decode_wire(z)), 0.0)
    assert zero_payload_like(b, "bf16").dtype == jnp.bfloat16
    assert zero_payload_like(b, "fp32").dtype == jnp.float32
    from jax.sharding import PartitionSpec as P
    spec = P("data", None, None)
    assert payload_spec(spec, "int8") == {"q": spec, "s": P("data", None)}
    assert payload_spec(spec, "fp32") == spec
    assert wire_itemsize("fp32", np.float32) == 4
    assert wire_itemsize("fp32", jnp.bfloat16) == 2
    assert wire_itemsize("bf16", np.float32) == 2
    assert wire_itemsize("int8", np.float32) == 1
    assert wire_itemsize("fp8", np.float32) == 1
    # decode path used by the kernels' jnp twin
    pay = encode_wire(b * 3, "int8", keys=wire_key(0, jnp.arange(2), 0))
    bits_eq(dequant_tiles(pay["q"], pay["s"]), decode_wire(pay))


# ---------------------------------------------------- bucket-subset schedule

def test_subset_schedule_rotation_and_mask_twin():
    for nb, n_send in ((3, 1), (5, 2), (8, 3)):
        sub = BucketSubsetSchedule(nb, n_send)
        assert sub.period == -(-nb // n_send)
        assert sub.fraction == n_send / nb
        sent = np.zeros(nb, bool)
        for t in range(sub.period):
            sel = sub.selected(t)
            assert sel.sum() == n_send
            sent |= sel
        assert sent.all(), (nb, n_send)  # full model diffuses every period
        for t in range(-2 * sub.period - 1, 2 * sub.period + 1):
            np.testing.assert_array_equal(
                np.asarray(sub.mask(jnp.int32(t))), sub.selected(t),
                err_msg=f"nb={nb} n_send={n_send} t={t}")


def test_build_subset_schedule_edges():
    assert build_subset_schedule(4, 1.0) is None
    assert build_subset_schedule(3, 0.99) is None  # rounds up to everything
    sub = build_subset_schedule(4, 0.5)
    assert sub.n_send == 2 and sub.period == 2
    assert build_subset_schedule(8, 0.01).n_send == 1  # floor of 1 bucket
    with pytest.raises(ValueError, match="fraction"):
        build_subset_schedule(4, 0.0)
    with pytest.raises(ValueError, match="n_send"):
        BucketSubsetSchedule(4, 4)
    assert wire_subset_of(WireFormat(subset=0.5), 4).n_send == 2
    assert wire_subset_of(WireFormat(), 4) is None


def test_wire_period_lcm():
    sched = build_schedule(8, num_rotations=2, seed=0)  # period 6
    assert wire_period(sched, None) == sched.period
    assert wire_period(sched, BucketSubsetSchedule(4, 1)) == \
        np.lcm(sched.period, 4)
    assert wire_period(sched, BucketSubsetSchedule(3, 2)) == \
        np.lcm(sched.period, 2)


# ------------------------------------------- oracle degeneracy + byte counts

def _global_buckets(p=8, seed=2, nb_hint=3):
    rng = np.random.default_rng(seed)
    tree = {"w1": jnp.asarray(rng.normal(size=(p, 5, 3)), jnp.float32),
            "w2": jnp.asarray(rng.normal(size=(p, 130)), jnp.float32),
            "w3": jnp.asarray(rng.normal(size=(p, 2, 7, 11)), jnp.float32),
            "w4": jnp.asarray(rng.normal(size=(p, 200)), jnp.float32)}
    layout = build_layout(tree, skip_leading=1, target_bucket_bytes=520)
    assert layout.num_buckets >= nb_hint
    assert layout.num_buckets % 2 == 0, layout.num_buckets
    return list(PackedParams.pack(tree, layout).buckets), layout


def test_quantized_oracle_default_wire_degenerates_to_pr1():
    """fp32 full-participation quantized oracle == the plain mix algebra
    bit-for-bit (static AND traced step)."""
    bufs, _ = _global_buckets()
    sched = build_schedule(8, seed=4)
    wire = WireFormat()
    for t in range(sched.period):
        recv = jnp.asarray(sched.recv_from(t))
        want = [((x.astype(jnp.float32) * 0.5
                  + x[recv].astype(jnp.float32) * 0.5).astype(x.dtype))
                for x in bufs]
        for tt in (t, jnp.int32(t)):
            got = jax.jit(lambda bs, _t=tt, _r=recv: gossip_mix_sim_quantized(
                bs, _r, _t, wire=wire))(bufs)
            for g, w in zip(got, want):
                bits_eq(g, w, f"t={t}")


def test_quantized_k_oracle_default_wire_degenerates_to_pr4():
    """fp32 full-participation ring oracle == gossip_mix_sim_delayed_k on
    the same buckets (after the zero-payload bootstrap drains: the wire ring
    boots with zero payloads, the PR-4 ring with param copies — both consume
    them only at alpha=0, so params agree every step and slots agree once
    every bootstrap slot is overwritten)."""
    bufs, _ = _global_buckets()
    k, p = 2, 8
    sched = build_schedule(p, seed=4)
    wire = WireFormat()
    # init_wire_inbox_ring only reads .buckets; give it a thin shim
    class _Shim:
        buckets = bufs
    ring_q = init_wire_inbox_ring(_Shim, k, p, wire)
    ring_l = init_inbox_ring(list(bufs), k, p)
    got, want = list(bufs), list(bufs)
    for t in range(sched.period + k + 1):
        recv = jnp.asarray(sched.recv_from(t))
        got, ring_q = gossip_mix_sim_quantized_k(got, ring_q, recv, wire=wire)
        want, ring_l = gossip_mix_sim_delayed_k(want, ring_l, recv)
        for g, w in zip(got, want):
            bits_eq(g, w, f"t={t}")
        np.testing.assert_array_equal(np.asarray(ring_q["valid"]),
                                      np.asarray(ring_l["valid"]))
        assert int(ring_q["t"]) == int(ring_l["t"])
        if t >= k:  # bootstrap slots drained: payloads must agree too
            for sq, sl in zip(ring_q["slots"], ring_l["slots"]):
                for g, w in zip(sq, sl):
                    bits_eq(g, w, f"slot t={t}")


def test_wire_bytes_per_step_ratios():
    """Acceptance accounting: int8 codes are exactly 4x fewer bytes than the
    fp32 wire, and a 50% bucket subset doubles that to 8x."""
    _, layout = _global_buckets()
    raw = wire_bytes_per_step(layout)
    assert raw["reduction_codes"] == 1.0 and raw["wire_dtype"] == "fp32"
    q = wire_bytes_per_step(layout, WireFormat(dtype="int8"))
    assert q["reduction_codes"] == 4.0
    assert q["code_bytes"] * 4 == raw["raw_bytes"]
    assert q["scale_bytes"] == sum(s // LANE for s in layout.strides) * 4
    # total (codes + scales) still well past the 4x headline at LANE=128
    assert q["reduction_total"] > 3.8
    sub = build_subset_schedule(layout.num_buckets, 0.5)
    qs = wire_bytes_per_step(layout, WireFormat(dtype="int8", subset=0.5))
    assert qs["subset_fraction"] == pytest.approx(sub.fraction)
    assert qs["reduction_codes"] == pytest.approx(4.0 / sub.fraction)
    assert qs["reduction_codes"] >= 8.0
    bf = wire_bytes_per_step(layout, WireFormat(dtype="bf16"))
    assert bf["reduction_codes"] == 2.0 and bf["scale_bytes"] == 0


# ------------------------------------------------ sim drift / loss acceptance

def _quadratic_loss(target):
    def loss(params, batch):
        return jnp.sum((params["w"] - target - batch) ** 2)
    return loss


def _run_wire_sim(wire_dtype="fp32", gossip_subset=1.0, p=8, steps=None,
                  lr=0.05, seed=3, staleness=1):
    sched = build_schedule(p, num_rotations=2, seed=seed)
    steps = steps if steps is not None else 6 * sched.period
    target = jnp.arange(4.0)
    loss = _quadratic_loss(target)
    opt = sgd(lr, momentum=0.0)
    params = replicate({"w": jnp.zeros(4)}, p)
    opt_state = opt.init(params)
    rng = np.random.default_rng(seed)
    bias = rng.normal(scale=1.0, size=(p, 4))
    step = make_async_sim_train_step(loss, opt, sched, staleness=staleness,
                                     wire_dtype=wire_dtype,
                                     gossip_subset=gossip_subset)
    ring = init_inbox_ring(params, staleness, p)
    hist = []
    for t in range(steps):
        batch = jnp.asarray(bias + rng.normal(scale=0.1, size=(p, 4)),
                            jnp.float32)
        opt_state, params, ring, m = step(opt_state, params, ring, batch,
                                          jnp.int32(t))
        hist.append({k: float(v) for k, v in m.items()})
    return params, hist


def test_quantized_sim_drift_and_loss_within_2x():
    """Acceptance: int8 / fp8 / 50%-sampled wires keep sim replica drift and
    final loss within 2x of the uncompressed wire (same seeds/batches)."""
    _, h_ref = _run_wire_sim()
    tail = 6
    drift_ref = max(np.mean([h["replica_variance"] for h in h_ref[-tail:]]),
                    1e-8)
    loss_ref = np.mean([h["loss"] for h in h_ref[-tail:]])
    for wd, frac in (("int8", 1.0), ("fp8", 1.0), ("int8", 0.5),
                     ("fp32", 0.5), ("bf16", 1.0)):
        _, h = _run_wire_sim(wire_dtype=wd, gossip_subset=frac)
        drift = np.mean([h["replica_variance"] for h in h[-tail:]])
        loss = np.mean([h["loss"] for h in h[-tail:]])
        assert drift <= 2.0 * drift_ref + 1e-6, (wd, frac, drift, drift_ref)
        assert loss <= 2.0 * loss_ref + 1e-6, (wd, frac, loss, loss_ref)


def test_default_wire_sim_is_bit_identical_to_legacy():
    """wire_dtype=fp32 + subset 1.0 through the sim factory is the EXACT
    legacy step (the science-mode branch must not perturb default runs)."""
    _, h_a = _run_wire_sim()
    _, h_b = _run_wire_sim(wire_dtype="fp32", gossip_subset=1.0)
    assert [h["loss"] for h in h_a] == [h["loss"] for h in h_b]


# -------------------------------------------------------- protocol plumbing

def test_protocol_wire_knobs_inert_at_dp1():
    from repro.core import make_protocol
    from repro.launch.mesh import make_smoke_mesh
    mesh = make_smoke_mesh(1, 1)
    proto = make_protocol("gossip", mesh, ("data",), {}, wire_dtype="int8",
                          gossip_subset=0.5)
    assert proto.wire is None and proto.period == 1
    tree = {"w": jnp.ones((1, 3))}
    assert proto.comm_params(tree, 0) is tree
    with pytest.raises(ValueError, match="wire dtype"):
        make_protocol("gossip", mesh, ("data",), {}, wire_dtype="int4")
    with pytest.raises(ValueError, match="subset fraction"):
        make_protocol("gossip", mesh, ("data",), {}, gossip_subset=0.0)


def test_dp1_wire_bundle_bitmatches_default(tiny_wire_bundle_factory):
    """At dp=1 the wire knobs are inert: int8 + 50% subset trains the exact
    same losses as the default wire."""
    ref = tiny_wire_bundle_factory("gossip")
    for wd, frac in (("int8", 0.5), ("fp8", 1.0)):
        got = tiny_wire_bundle_factory("gossip", wire_dtype=wd,
                                       gossip_subset=frac)
        np.testing.assert_array_equal(ref, got)


@pytest.fixture
def tiny_wire_bundle_factory():
    import dataclasses
    from repro.configs import get_config
    from repro.data import ShardedTokenDataset
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.specs import train_input_specs
    from repro.models import reduced
    from repro.train import (Trainer, init_train_state, make_distribution,
                             make_train_step_bundle)

    def run(protocol, steps=3, wire_dtype="fp32", gossip_subset=1.0,
            staleness=1):
        cfg = dataclasses.replace(
            reduced(get_config("qwen3-0.6b"), d_model=64),
            param_dtype="float32", compute_dtype="float32")
        dist = make_distribution(make_smoke_mesh(1, 1), "replica")
        opt = sgd(0.3, momentum=0.9)
        ss, sa, bs = train_input_specs(cfg, dist, 24, 4, opt)
        bundle = make_train_step_bundle(
            cfg, dist, opt, state_shapes=ss, state_axes=sa, batch_shapes=bs,
            protocol=protocol, remat=False, gossip_packed=True,
            staleness=staleness, wire_dtype=wire_dtype,
            gossip_subset=gossip_subset)
        state, _ = init_train_state(
            jax.random.key(0), cfg, dist, opt, packed=True,
            layout=bundle.layout, inbox=bundle.protocol.staleness,
            wire=bundle.wire)
        ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=24, n_shards=1,
                                 batch_per_shard=4, seed=0)
        return [h["loss"] for h in
                Trainer(bundle, state, ds, log_every=0).run(steps)]

    return run


# ------------------------------------------------- wire-ring checkpointing

def _wire_ring_state(wire, k=2, dp=4, seed=7, step=9):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    tree = {"w1": mk(dp, 5, 3), "w2": mk(dp, 130)}
    packed = PackedParams.pack(tree, skip_leading=1)
    ring = init_wire_inbox_ring(packed, k, dp, wire)
    # fill the slots with real encoded payloads so the roundtrip is nontrivial
    slots = []
    for j in range(k):
        slot = []
        for i, b in enumerate(packed.buckets):
            pay = encode_wire(b + float(j + 1), wire.dtype,
                              keys=wire_key(j, jnp.arange(dp), i, 0))
            slot.append(pay)
        slots.append(tuple(slot))
    ring = {"slots": tuple(slots),
            "valid": jnp.asarray(rng.integers(0, 2, (dp, k)), jnp.float32),
            "t": jnp.asarray(step, jnp.int32)}
    return {"params": packed, "opt": {"step": jnp.int32(step)},
            "inbox": ring}, tree


@pytest.mark.parametrize("wire_dtype", ["int8", "fp8", "bf16"])
def test_wire_ring_checkpoint_roundtrip(tmp_path, wire_dtype):
    """Encoded ring slots persist bit-exactly: int8 codes save natively,
    fp8/bf16 stage through fp32 losslessly, scales ride along."""
    from repro.checkpoint import restore_state, save_state
    wire = WireFormat(dtype=wire_dtype)
    state, _ = _wire_ring_state(wire)
    d = str(tmp_path / "ck")
    save_state(d, state, step=9, metadata={"wire_dtype": wire_dtype})
    rest, man = restore_state(d, state)
    assert man["metadata"]["wire_dtype"] == wire_dtype
    assert len(rest["inbox"]["slots"]) == 2
    for sg, sw in zip(rest["inbox"]["slots"], state["inbox"]["slots"]):
        for pg, pw in zip(sg, sw):
            for lg, lw in zip(jax.tree.leaves(pg), jax.tree.leaves(pw)):
                bits_eq(lg, lw, wire_dtype)
    bits_eq(rest["inbox"]["valid"], state["inbox"]["valid"])
    assert int(rest["inbox"]["t"]) == 9


def test_cross_wire_format_restore_resets_ring(tmp_path):
    """Restoring a checkpoint whose ring was encoded under a DIFFERENT wire
    format keeps params/optimizer bit-exact and resets the ring to the
    template's bootstrap (all-invalid, zero payloads) with the dispatch
    counter resumed from the manifest step — in-flight compressed payloads
    are declared lost on the wire, exactly a k-step timeout burst."""
    from repro.checkpoint import restore_state, save_state
    state8, tree = _wire_ring_state(WireFormat(dtype="int8"), step=9)
    d = str(tmp_path / "ck8")
    save_state(d, state8, step=9, metadata={"wire_dtype": "int8"})

    # int8 ring -> fp32-wire (PR-4 param-tree slots) template
    packed = PackedParams.pack(tree, skip_leading=1)
    tpl = {"params": PackedParams.pack(
               jax.tree.map(lambda x: x * 0.0, tree), skip_leading=1),
           "opt": {"step": jnp.int32(0)},
           "inbox": init_inbox_ring(packed, 2, 4)}
    rest, man = restore_state(d, tpl)
    got = rest["params"].unpack() if hasattr(rest["params"], "unpack") \
        else rest["params"]
    for k_ in tree:
        np.testing.assert_array_equal(np.asarray(got[k_]),
                                      np.asarray(tree[k_]))
    v = np.asarray(rest["inbox"]["valid"])
    assert v.shape == (4, 2) and not v.any()
    assert int(rest["inbox"]["t"]) == 9

    # ...and fp32-wire ring -> int8-wire template (the reverse migration)
    legacy = {"params": packed, "opt": {"step": jnp.int32(11)},
              "inbox": dict(init_inbox_ring(packed, 2, 4),
                            t=jnp.asarray(11, jnp.int32))}
    d2 = str(tmp_path / "cklegacy")
    save_state(d2, legacy, step=11, metadata={"wire_dtype": "fp32"})
    tpl8 = {"params": PackedParams.pack(
                jax.tree.map(lambda x: x * 0.0, tree), skip_leading=1),
            "opt": {"step": jnp.int32(0)},
            "inbox": init_wire_inbox_ring(packed, 2, 4,
                                          WireFormat(dtype="int8"))}
    rest8, _ = restore_state(d2, tpl8)
    assert not np.asarray(rest8["inbox"]["valid"]).any()
    assert int(rest8["inbox"]["t"]) == 11
    for slot in rest8["inbox"]["slots"]:
        for pay in slot:
            assert isinstance(pay, dict)
            np.testing.assert_array_equal(np.asarray(decode_wire(pay)), 0.0)


# ---------------- p=8 subprocess: all four wired engines == the oracles

_EQUIV_SCRIPT = r"""
import os, functools
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import (build_schedule, build_layout, PackedParams,
                        exchange_ok, init_wire_inbox_ring,
                        make_packed_gossip_mix, make_packed_async_gossip_mix,
                        make_packed_fused_update,
                        make_packed_fused_async_update,
                        gossip_mix_sim_quantized, gossip_mix_sim_quantized_k,
                        wire_period, wire_subset_of)
from repro.kernels import gossip_mix_wire_bucket
from repro.kernels.quantize import (WireFormat, decode_wire, encode_wire,
                                    wire_key, zero_payload_like)
from repro.optim import sgd

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
# small bucket cap -> multiple buckets, so subsets actually rotate
layout = build_layout(tree, skip_leading=1, target_bucket_bytes=520)
nb = layout.num_buckets
assert nb >= 3, nb

def bits_eq(a, b, msg=""):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype, (a.dtype, b.dtype, msg)
    np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8),
                                  err_msg=str(msg))

def payload_eq(a, b, msg=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        bits_eq(la, lb, msg)

THIRD = 1.0 / 3.0

# ---- sync unfused packed engine == gossip_mix_sim_quantized, every phase
SYNC = [("int8", 1.0, "static", None), ("int8", THIRD, "static", None),
        ("fp8", THIRD, "static", None), ("bf16", 1.0, "static", None),
        ("fp32", THIRD, "static", None), ("int8", THIRD, "dynamic", None),
        ("int8", THIRD, "static", gossip_mix_wire_bucket)]
for wd, frac, mode, impl in SYNC:
    wire = WireFormat(dtype=wd, subset=frac, seed=5)
    eng = make_packed_gossip_mix(mesh, ("data",), sched, layout, mode=mode,
                                 mix_impl=impl, wire=wire)
    eff = wire_period(sched, wire_subset_of(wire, nb))
    got = PackedParams.pack(tree, layout)
    want = list(PackedParams.pack(tree, layout).buckets)
    for t in range(eff + 2):
        ph = (t if mode == "static" else jnp.int32(t))
        got = jax.jit(functools.partial(eng, phase=ph))(got)
        recv = jnp.asarray(sched.recv_from(t % sched.period))
        want = jax.jit(lambda bs, _t=t % eff, _r=recv:
                       gossip_mix_sim_quantized(bs, _r, _t, wire=wire))(want)
        for i, (g, w) in enumerate(zip(got.buckets, want)):
            bits_eq(g, w, f"sync {wd} frac={frac} mode={mode} t={t} b={i}")
    print(f"ok sync {wd} frac={frac:.2f} mode={mode} "
          f"impl={'pallas' if impl else 'jnp'}")

# fp32-wire full participation delegates to the PR-1..5 engine exactly
dflt = make_packed_gossip_mix(mesh, ("data",), sched, layout,
                              wire=WireFormat())
legacy = make_packed_gossip_mix(mesh, ("data",), sched, layout)
a = jax.jit(functools.partial(dflt, phase=0))(PackedParams.pack(tree, layout))
b = jax.jit(functools.partial(legacy, phase=0))(
    PackedParams.pack(tree, layout))
for x, y in zip(a.buckets, b.buckets):
    bits_eq(x, y, "default-wire PR-5 parity")
print("ok default-wire parity")

# ---- async unfused packed engine == gossip_mix_sim_quantized_k
class _Global:
    buckets = list(PackedParams.pack(tree, layout).buckets)

ASYNC = [(1, 0.0, "static", "int8", THIRD), (2, 0.35, "static", "int8", THIRD),
         (4, 0.0, "static", "fp8", 1.0), (2, 0.0, "dynamic", "int8", THIRD),
         (4, 0.35, "static", "bf16", THIRD)]
for k, rate, mode, wd, frac in ASYNC:
    wire = WireFormat(dtype=wd, subset=frac, seed=5)
    eng = make_packed_async_gossip_mix(
        mesh, ("data",), sched, layout, staleness=k, drop_rate=rate,
        drop_seed=3, mode=mode, wire=wire)
    eff = wire_period(sched, wire_subset_of(wire, nb))
    got = PackedParams.pack(tree, layout)
    ring_g = init_wire_inbox_ring(got, k, p, wire)
    want = list(PackedParams.pack(tree, layout).buckets)
    ring_w = init_wire_inbox_ring(_Global, k, p, wire)
    for t in range(eff + k + 1):
        ph = (t if mode == "static" else jnp.int32(t))
        got, ring_g = jax.jit(functools.partial(eng, phase=ph))(got, ring_g)
        ok = exchange_ok(ring_w["t"], jnp.arange(p), 3, rate)
        recv = jnp.asarray(sched.recv_from(t % sched.period))
        want, ring_w = jax.jit(
            lambda bs, rg, _r=recv, _ok=ok: gossip_mix_sim_quantized_k(
                bs, rg, _r, wire=wire, ok=_ok))(want, ring_w)
        msg = f"async {wd} frac={frac} k={k} rate={rate} mode={mode} t={t}"
        for g, w in zip(got.buckets, want):
            bits_eq(g, w, msg)
        bits_eq(ring_g["valid"], ring_w["valid"], msg)
        assert int(ring_g["t"]) == int(ring_w["t"])
        for sg, sw in zip(ring_g["slots"], ring_w["slots"]):
            payload_eq(sg, sw, msg + " slot")
    print(f"ok async {wd} frac={frac:.2f} k={k} rate={rate} mode={mode}")

# ---- fused sync engine == [wire mix of RAW params ; tree-level update]
opt = sgd(0.1, momentum=0.9)
grads = PackedParams.pack(jax.tree.map(lambda x: x * 0.1 + 0.01, tree),
                          layout)
for wd, frac in (("int8", THIRD), ("fp8", 1.0)):
    wire = WireFormat(dtype=wd, subset=frac, seed=5)
    sub = wire_subset_of(wire, nb)
    eff = wire_period(sched, sub)
    eng = make_packed_fused_update(mesh, ("data",), sched, layout, opt,
                                   alpha=0.5, wire=wire)
    def ref_step(rp, g, rst, *, t):
        ph = t % eff
        sel = sub.selected(ph) if sub is not None else np.ones(nb, bool)
        recv = jnp.asarray(sched.recv_from(t % sched.period))
        bufs = []
        for i, b in enumerate(rp.buckets):
            if not sel[i]:
                bufs.append(b)
                continue
            enc = encode_wire(b, wire.dtype,
                              keys=wire_key(ph, jnp.arange(p), i, wire.seed))
            pay = jax.tree.map(lambda e: e[recv], enc)
            q = decode_wire(pay)
            bufs.append((b.astype(jnp.float32) * 0.5
                         + q.astype(jnp.float32) * 0.5).astype(b.dtype))
        return opt.update(PackedParams(bufs, layout), g, rst)
    params = PackedParams.pack(tree, layout); st = opt.init(params)
    rp = PackedParams.pack(tree, layout); rst = opt.init(rp)
    for t in range(eff + 2):
        params, st = jax.jit(functools.partial(eng, phase=t))(
            params, grads, st)
        rp, rst = jax.jit(functools.partial(ref_step, t=t))(rp, grads, rst)
        msg = f"fused-sync {wd} frac={frac} t={t}"
        for g, w in zip(params.buckets, rp.buckets):
            bits_eq(g, w, msg)
        for g, w in zip(st["mom"].buckets, rst["mom"].buckets):
            bits_eq(g, w, msg + " mom")
    print(f"ok fused-sync {wd} frac={frac:.2f}")

# ---- fused async engine == [masked wire mix of ring slot ; update] + FIFO
for k, rate, wd, frac, mode in ((1, 0.0, "int8", THIRD, "static"),
                                (2, 0.35, "int8", THIRD, "static"),
                                (4, 0.0, "fp8", 1.0, "static"),
                                (2, 0.0, "int8", THIRD, "dynamic")):
    wire = WireFormat(dtype=wd, subset=frac, seed=5)
    sub = wire_subset_of(wire, nb)
    eff = wire_period(sched, sub)
    eng = make_packed_fused_async_update(
        mesh, ("data",), sched, layout, opt, alpha=0.5, staleness=k,
        drop_rate=rate, drop_seed=3, mode=mode, wire=wire)
    def ref_step(rp, g, ring, rst, ok, *, t):
        slots, valid, tt = ring["slots"], ring["valid"], ring["t"]
        a = 0.5 * valid[:, 0]
        sel_cons = (sub.selected(t - k) if sub is not None
                    else np.ones(nb, bool))
        sel_send = (sub.selected(t) if sub is not None
                    else np.ones(nb, bool))
        recv = jnp.asarray(sched.recv_from(t % sched.period))
        outbox = []
        for i, b in enumerate(rp.buckets):
            if sel_send[i]:
                enc = encode_wire(
                    b, wire.dtype,
                    keys=wire_key(tt, jnp.arange(p), i, wire.seed))
                outbox.append(jax.tree.map(lambda e: e[recv], enc))
            else:
                outbox.append(zero_payload_like(b, wire.dtype))
        bufs = []
        for i, b in enumerate(rp.buckets):
            if sel_cons[i]:
                q = decode_wire(slots[0][i])
                w = a.reshape((p,) + (1,) * (b.ndim - 1))
                bufs.append((b.astype(jnp.float32) * (1.0 - w)
                             + q.astype(jnp.float32) * w).astype(b.dtype))
            else:
                bufs.append(b)
        new_p, new_st = opt.update(PackedParams(bufs, layout), g, rst)
        ring2 = {"slots": tuple(slots[1:]) + (tuple(outbox),),
                 "valid": jnp.concatenate([valid[:, 1:], ok[:, None]], 1),
                 "t": tt + 1}
        return new_p, new_st, ring2
    params = PackedParams.pack(tree, layout); st = opt.init(params)
    ring = init_wire_inbox_ring(params, k, p, wire)
    rp = PackedParams.pack(tree, layout); rst = opt.init(rp)
    rring = init_wire_inbox_ring(_Global, k, p, wire)
    for t in range(eff + k + 1):
        ph = (t if mode == "static" else jnp.int32(t))
        params, st, ring = jax.jit(functools.partial(eng, phase=ph))(
            params, grads, ring, st)
        ok = exchange_ok(rring["t"], jnp.arange(p), 3, rate)
        rp, rst, rring = jax.jit(functools.partial(ref_step, t=t))(
            rp, grads, rring, rst, ok)
        msg = f"fused-async {wd} frac={frac} k={k} rate={rate} t={t}"
        for g, w in zip(params.buckets, rp.buckets):
            bits_eq(g, w, msg)
        for g, w in zip(st["mom"].buckets, rst["mom"].buckets):
            bits_eq(g, w, msg + " mom")
        bits_eq(ring["valid"], rring["valid"], msg)
        for sg, sw in zip(ring["slots"], rring["slots"]):
            payload_eq(sg, sw, msg + " slot")
    print(f"ok fused-async {wd} frac={frac:.2f} k={k} rate={rate} "
          f"mode={mode}")
print("ALL_OK")
"""


@pytest.mark.slow
def test_wired_engines_match_quantized_oracles_p8():
    """Acceptance: the compressed + partition-sampled shard_map engines ==
    the ``gossip_mix_sim_quantized`` / ``_quantized_k`` oracles bit-exactly
    at p=8 — int8/fp8/bf16 wires, full and rotating 1/3 subsets, sync
    (unfused + fused, incl. the Pallas in-sweep decode mix) and async
    (k in {1,2,4}, drops on/off, unfused + fused), static + dynamic phase
    selection, params + momenta + every encoded ring slot; the default wire
    reproduces the PR-5 engine exactly."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", _EQUIV_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert "ALL_OK" in r.stdout


_FSDP_SCRIPT = r"""
import os, functools
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import (build_schedule, build_layout, PackedParams,
                        exchange_ok, init_wire_inbox_ring,
                        make_packed_gossip_mix, make_packed_async_gossip_mix,
                        gossip_mix_sim_quantized, gossip_mix_sim_quantized_k,
                        wire_period, wire_subset_of)
from repro.kernels.quantize import WireFormat

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
inner = {"emb": P("data", None), "ffn": P("model", None, None),
         "norm": P(None), "b": P(None)}
layout = build_layout(tree, skip_leading=1, shard_axes=("data", "model"),
                      shard_axis_sizes=(2, 2), shard_specs=inner,
                      target_bucket_bytes=512)
nb = layout.num_buckets
assert layout.num_shards == 4 and nb >= 2, (layout.num_shards, nb)

def bits_eq(a, b, msg=""):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype, (a.dtype, b.dtype, msg)
    np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8),
                                  err_msg=str(msg))

# the shard-local engine keys noise by GLOBAL element index, so the global
# single-array oracle must agree bit-for-bit even though each device
# encodes only its stride
for wd, frac in (("int8", 1.0), ("int8", 0.5), ("fp8", 0.5)):
    wire = WireFormat(dtype=wd, subset=frac, seed=5)
    eng = make_packed_gossip_mix(mesh, ("pod",), sched, layout, wire=wire)
    eff = wire_period(sched, wire_subset_of(wire, nb))
    got = PackedParams.pack(tree, layout)
    want = list(PackedParams.pack(tree, layout).buckets)
    for t in range(eff + 1):
        got = jax.jit(functools.partial(eng, phase=t))(got)
        recv = jnp.asarray(sched.recv_from(t % sched.period))
        want = jax.jit(lambda bs, _t=t % eff, _r=recv:
                       gossip_mix_sim_quantized(bs, _r, _t, wire=wire))(want)
        for i, (g, w) in enumerate(zip(got.buckets, want)):
            bits_eq(g, w, f"fsdp sync {wd} frac={frac} t={t} b={i}")
    print(f"ok fsdp sync {wd} frac={frac}")

class _Global:
    buckets = list(PackedParams.pack(tree, layout).buckets)

for k, rate, wd, frac in ((2, 0.0, "int8", 0.5), (1, 0.4, "int8", 1.0)):
    wire = WireFormat(dtype=wd, subset=frac, seed=5)
    eng = make_packed_async_gossip_mix(
        mesh, ("pod",), sched, layout, staleness=k, drop_rate=rate,
        drop_seed=5, wire=wire)
    eff = wire_period(sched, wire_subset_of(wire, nb))
    got = PackedParams.pack(tree, layout)
    ring_g = init_wire_inbox_ring(got, k, p, wire)
    want = list(PackedParams.pack(tree, layout).buckets)
    ring_w = init_wire_inbox_ring(_Global, k, p, wire)
    for t in range(eff + k + 1):
        got, ring_g = jax.jit(functools.partial(eng, phase=t))(got, ring_g)
        ok = exchange_ok(ring_w["t"], jnp.arange(p), 5, rate)
        recv = jnp.asarray(sched.recv_from(t % sched.period))
        want, ring_w = jax.jit(
            lambda bs, rg, _r=recv, _ok=ok: gossip_mix_sim_quantized_k(
                bs, rg, _r, wire=wire, ok=_ok))(want, ring_w)
        msg = f"fsdp async {wd} frac={frac} k={k} rate={rate} t={t}"
        for g, w in zip(got.buckets, want):
            bits_eq(g, w, msg)
        bits_eq(ring_g["valid"], ring_w["valid"], msg)
        for sg, sw in zip(ring_g["slots"], ring_w["slots"]):
            for pg, pw in zip(sg, sw):
                for lg, lw in zip(jax.tree.leaves(pg), jax.tree.leaves(pw)):
                    bits_eq(lg, lw, msg + " slot")
    print(f"ok fsdp async {wd} frac={frac} k={k} rate={rate}")
print("FSDP_OK")
"""


@pytest.mark.slow
def test_wired_engines_fsdp_shard_local_p8():
    """Acceptance: the compressed wire under the PR-5 hierarchical
    shard-local layout ((2,2,2) pod/data/model mesh, FSDP+TP inside the
    replica) == the global single-array oracles bit-exactly — each device
    encodes only its stride but keys noise by global element index."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", _FSDP_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert "FSDP_OK" in r.stdout


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

def make(protocol, wire_dtype="fp32", subset=1.0, k=1, drop=0.0, n_seed=0,
         fused=None):
    bundle = make_train_step_bundle(
        cfg, dist, opt, state_shapes=ss, state_axes=sa, batch_shapes=bs,
        protocol=protocol, remat=False, gossip_packed=True, staleness=k,
        drop_rate=drop, wire_dtype=wire_dtype, gossip_subset=subset,
        fused_update=fused)
    state, _ = init_train_state(jax.random.key(n_seed), cfg, dist, opt,
                                packed=True, layout=bundle.layout,
                                inbox=bundle.protocol.staleness,
                                wire=bundle.wire)
    ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=16, n_shards=8,
                             batch_per_shard=2, seed=0)
    return bundle, state, ds

# fp32 wire knobs reproduce the PR-5 trajectory EXACTLY (sync + async)
for proto in ("gossip", "gossip_async"):
    b0, s0, d0 = make(proto)
    h0 = [h["loss"] for h in Trainer(b0, s0, d0, log_every=0).run(4)]
    bw, sw, dw = make(proto, wire_dtype="fp32", subset=1.0)
    assert bw.wire is None
    hw = [h["loss"] for h in Trainer(bw, sw, dw, log_every=0).run(4)]
    np.testing.assert_array_equal(np.asarray(h0), np.asarray(hw))
    print(f"ok pr5-parity {proto}")

# straight vs save/restore/continue, compressed + sampled, sync and async
for proto, wd, sub, k, drop in (("gossip", "int8", 0.5, 1, 0.0),
                                ("gossip_async", "int8", 0.5, 2, 0.2),
                                ("gossip_async", "fp8", 1.0, 1, 0.0)):
    bundle, state, ds = make(proto, wd, sub, k, drop)
    assert bundle.wire is not None
    per = bundle.protocol.period
    hist_straight = Trainer(bundle, state, ds, log_every=0).run(8)

    bundle, state, ds = make(proto, wd, sub, k, drop)
    tr1 = Trainer(bundle, state, ds, log_every=0)
    tr1.run(4)
    ckdir = tempfile.mkdtemp()
    save_state(ckdir, tr1.state, step=4,
               metadata={"protocol": proto, "staleness": k,
                         "wire_dtype": wd, "gossip_subset": sub,
                         "phase": 4 % per})
    bundle2, state2, ds2 = make(proto, wd, sub, k, drop, n_seed=1)
    restored, man = restore_state(ckdir, state2)
    tr2 = Trainer(bundle2, restored, ds2, log_every=0)
    hist_resumed = tr2.run(4, start_step=man["step"])
    a = [h["loss"] for h in hist_straight[4:]]
    b = [h["loss"] for h in hist_resumed]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print(f"ok e2e {proto} {wd} sub={sub} k={k} drop={drop}")

# cross-wire interchange through the real stack: an int8-wire async ring
# checkpoint boots (a) an fp32-wire run and (b) an unfused int8 run; an
# fp32-wire checkpoint boots an int8-wire run (ring reset, params exact)
bundle, state, ds = make("gossip_async", "int8", 0.5, k=2)
tr = Trainer(bundle, state, ds, log_every=0)
tr.run(4)
ck8 = tempfile.mkdtemp()
save_state(ck8, tr.state, step=4, metadata={"protocol": "gossip_async",
                                            "staleness": 2,
                                            "wire_dtype": "int8"})
for wd2, sub2, fused in (("fp32", 1.0, None), ("int8", 0.5, False)):
    b2, s2, ds2 = make("gossip_async", wd2, sub2, k=2, n_seed=3, fused=fused)
    r2, man = restore_state(ck8, s2)
    if wd2 == "fp32":
        assert not np.asarray(r2["inbox"]["valid"]).any()  # ring reset
        assert int(r2["inbox"]["t"]) == 4
    for x, y in zip(jax.tree.leaves(tr.state["params"]),
                    jax.tree.leaves(r2["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    h = Trainer(b2, r2, ds2, log_every=0).run(3, start_step=man["step"])
    assert all(np.isfinite(x["loss"]) for x in h)
    print(f"ok cross-restore int8 -> {wd2} fused={fused is None}")

b3, s3, ds3 = make("gossip_async", "fp32", 1.0, k=2, n_seed=4)
tr3 = Trainer(b3, s3, ds3, log_every=0)
tr3.run(4)
ck32 = tempfile.mkdtemp()
save_state(ck32, tr3.state, step=4, metadata={"protocol": "gossip_async",
                                              "staleness": 2,
                                              "wire_dtype": "fp32"})
b4, s4, ds4 = make("gossip_async", "int8", 0.5, k=2, n_seed=5)
r4, man = restore_state(ck32, s4)
assert not np.asarray(r4["inbox"]["valid"]).any()
assert int(r4["inbox"]["t"]) == 4
for sl in r4["inbox"]["slots"]:
    for pay in sl:
        assert isinstance(pay, dict) and pay["q"].dtype == jnp.int8
h = Trainer(b4, r4, ds4, log_every=0).run(3, start_step=man["step"])
assert all(np.isfinite(x["loss"]) for x in h)
print("ok cross-restore fp32 -> int8")
print("E2E_OK")
"""


@pytest.mark.slow
def test_wire_train_checkpoint_resume_p8():
    """Acceptance: compressed + sampled wires train end to end at p=8
    through the real bundle/trainer/checkpoint stack; fp32 wire knobs
    reproduce the PR-5 trajectories bit-exactly; checkpoint-resume is
    bit-deterministic with encoded ring slots; cross-wire-format restores
    keep params exact and reset the ring (in-flight payloads declared lost
    on the wire)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", _E2E_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert "E2E_OK" in r.stdout
