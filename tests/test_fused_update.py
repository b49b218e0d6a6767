"""Fused mix+apply update engine (kernels/fused_update.py + the packed
fused engines in core/gossip.py / core/async_gossip.py).

Covers: bucket-level fused-vs-unfused equivalence for all three optimizers
(sgd / adamw / lars) x fp32/bf16 buckets x alpha in {0, 0.5}, with the
Pallas-interpret kernel and the jnp twin bit-identical to each other;
ragged-tail buffers through the kernel's epilogue; (subprocess, 8 forced
host devices) sync + async engine == the unfused mix-then-apply composition
bit-exactly at p=8 across every schedule phase, static + dynamic; a jaxpr
assertion that the fused step contains no standalone mix kernel and no
optimizer add/mul sweep over full buckets outside the fused kernel; and
dp=1 bundle-level equality fused vs unfused.

Note on comparisons: both sides of every equivalence run under jit — XLA's
FMA contraction differs between compiled and op-by-op eager execution, so
eager references can drift by 1 ulp even in fp32.  bf16 buckets get a
small tolerance (the tree-level sgd runs its momentum arithmetic in bf16,
the fused kernel accumulates in fp32 — a <= 1-2 ulp difference).

Note on LARS at dp > 1: the tree-level update computes its norms over the
GLOBAL replica-stacked leaves, while the fused engine's norm prepass runs
per replica (each rank owns a distinct model, paper §4) — the two agree
exactly at dp == 1, which is what the bucket-level suite pins down.
"""
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.buckets import LANE, PackedParams, build_layout
from repro.optim import adamw, lars, sgd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BF16_TOL = 2e-2  # ~2 bf16 ulps relative


def _odd_tree(dtype, lead=()):
    rng = np.random.default_rng(7)
    mk = lambda *s: jnp.asarray(rng.normal(size=lead + s), jnp.float32).astype(dtype)
    return {"w1": mk(5, 3), "w2": mk(130,), "w3": mk(2, 7, 11), "b": mk(1,)}


def _optimizers():
    return [
        ("sgd", sgd(0.1, momentum=0.9, weight_decay=1e-4)),
        ("sgd_plain", sgd(0.1, momentum=0.0)),
        ("adamw", adamw(0.01, weight_decay=0.02)),
        ("lars", lars(0.1, momentum=0.9, weight_decay=1e-4)),
    ]


def _moments(opt, state):
    return tuple(state[k] for k in opt.fused_moments)


def _ref_step(opt, layout, params, grads, state, partner, alpha):
    """The unfused mix-then-apply composition: standalone bucket mix (the
    gossip_mix arithmetic, materialized in the bucket dtype) followed by the
    tree-level optimizer.update."""
    if partner is not None and alpha != 0.0:
        mixed = PackedParams(
            [(b.astype(jnp.float32) * (1.0 - alpha)
              + q.astype(jnp.float32) * alpha).astype(b.dtype)
             for b, q in zip(params.buckets, partner.buckets)], layout)
    else:
        mixed = params
    return opt.update(mixed, grads, state)


def _fused_step(opt, layout, params, grads, state, partner, alpha, impl):
    new_buckets, new_state = [], {"step": state["step"] + 1}
    moms_out = [[] for _ in opt.fused_moments]
    for i in range(layout.num_buckets):
        moms = tuple(state[k].buckets[i] if state[k] is not None else None
                     for k in opt.fused_moments)
        p2, m2 = opt.fused_update(
            i, params.buckets[i], grads.buckets[i],
            partner.buckets[i] if partner is not None else None, moms,
            step=state["step"], alpha=alpha, layout=layout, impl=impl)
        new_buckets.append(p2)
        for j, mv in enumerate(m2):
            moms_out[j].append(mv)
    for j, k in enumerate(opt.fused_moments):
        new_state[k] = (PackedParams(moms_out[j], layout)
                        if state[k] is not None else None)
    return PackedParams(new_buckets, layout), new_state


@pytest.mark.parametrize("opt_name,opt", _optimizers())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("alpha", [0.0, 0.5])
def test_fused_bucket_matches_unfused_composition(opt_name, opt, dtype, alpha):
    """fused_update == standalone mix + tree-level update, per bucket, for
    3 steps (so momenta/bias corrections are exercised), jnp impl and
    Pallas-interpret impl both."""
    assert opt.fused_update is not None
    assert opt.fused_moments in (("mom",), ("m", "v"))
    tree = _odd_tree(dtype)
    grads = jax.tree.map(lambda x: x * 0.1 + jnp.asarray(0.01, x.dtype), tree)
    layout = build_layout(tree)
    params = PackedParams.pack(tree, layout)
    gp = PackedParams.pack(grads, layout)
    # a real mix partner is a ppermute of packed params: zero in the
    # alignment-padding regions (packed at the leaf level, not bucket level)
    partner = PackedParams.pack(
        jax.tree.map(lambda x: x + jnp.asarray(0.02, x.dtype), tree), layout)

    ref = jax.jit(functools.partial(_ref_step, opt, layout, alpha=alpha))
    fus = {impl: jax.jit(functools.partial(_fused_step, opt, layout,
                                           alpha=alpha, impl=impl))
           for impl in ("jnp", "pallas")}

    rp, rst = params, opt.init(params)
    fp = {impl: params for impl in fus}
    fst = {impl: opt.init(params) for impl in fus}
    for _ in range(3):
        rp, rst = ref(params=rp, grads=gp, state=rst, partner=partner)
        for impl in fus:
            fp[impl], fst[impl] = fus[impl](params=fp[impl], grads=gp,
                                            state=fst[impl], partner=partner)
        # both impls keep the buckets' (rows, 128) storage shape
        for impl in fus:
            assert [b.shape for b in fp[impl].buckets] == [
                layout.bucket_shape(i) for i in range(layout.num_buckets)]
        # jnp impl vs pallas-interpret impl: identical programs, bit-equal
        for a, b in zip(fp["jnp"].buckets, fp["pallas"].buckets):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        for k in opt.fused_moments:
            if fst["jnp"][k] is None:
                assert fst["pallas"][k] is None and rst[k] is None
                continue
            for a, b in zip(fst["jnp"][k].buckets, fst["pallas"][k].buckets):
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b, np.float32))
        # fused vs the unfused composition
        for a, b in zip(fp["jnp"].buckets, rp.buckets):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            if dtype == jnp.float32:
                np.testing.assert_array_equal(a, b)
            else:
                np.testing.assert_allclose(a, b, rtol=BF16_TOL, atol=BF16_TOL)
        for k in opt.fused_moments:
            if rst[k] is None:
                continue
            for a, b in zip(fst["jnp"][k].buckets, rst[k].buckets):
                a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
                if dtype != jnp.float32:
                    np.testing.assert_allclose(a, b, rtol=BF16_TOL,
                                               atol=BF16_TOL)
                elif opt_name == "lars":
                    # the trust ratio broadcasts as a scalar per leaf in the
                    # tree-level update but as a per-row tile in the fused
                    # kernel; XLA picks different FMA contractions for
                    # mu*m + g*trust — <= 1 fp32 ulp on the moment buffer
                    # (params still compare bit-equal above)
                    np.testing.assert_allclose(a, b, rtol=2e-7, atol=1e-12)
                else:
                    np.testing.assert_array_equal(a, b)
        assert int(fst["jnp"]["step"]) == int(rst["step"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("alpha", [0.5, 0.0])
def test_masked_alpha_matches_static(dtype, alpha):
    """The masked-alpha variant (alpha as a traced coefficient — the
    bounded-delay runtime's skip-on-timeout path) computes the same numbers
    as the statically-baked alpha: bit-identical on the jnp twins (the CPU
    production path the async engines run) and the standalone gossip-mix
    kernel; the Pallas-INTERPRET fused kernels land within 1-2 fp32 ulps of
    their twins, because XLA:CPU picks different FMA contractions for the
    mix-update chain when the multiplier is a parameter instead of a
    constant (the same compiled-vs-eager caveat noted in the module
    docstring — on TPU the kernel is compiled by Mosaic, not this path).
    The bit-exactness that matters — engines == oracle with BOTH on the
    traced form — is pinned by the p=8 subprocess suites."""
    from repro.kernels.fused_update import (fused_adamw_1d, fused_adamw_ref,
                                            fused_lars_ref, fused_sgd_1d,
                                            fused_sgd_ref)
    from repro.kernels.gossip_mix import gossip_mix_1d
    rng = np.random.default_rng(5)
    n = 4 * LANE
    mk = lambda: jnp.asarray(rng.normal(size=(n,)), jnp.float32).astype(dtype)
    p, g, b, mom = mk(), mk(), mk(), mk()
    lr = jnp.float32(0.1)
    al_t = jnp.float32(alpha)

    def bit_eq(xs, ys):
        for x, y in zip(xs, ys):
            if x is None:
                assert y is None
                continue
            np.testing.assert_array_equal(np.asarray(x, np.float32),
                                          np.asarray(y, np.float32))

    # jnp twins: traced alpha == static alpha bitwise
    fn = functools.partial(fused_sgd_ref, weight_decay=1e-4)
    bit_eq(jax.jit(functools.partial(fn, alpha=alpha))(p, g, b, mom, lr=lr),
           jax.jit(lambda *a, **kw: fn(*a, alpha=al_t, **kw))(p, g, b, mom,
                                                              lr=lr))
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    aargs = dict(lr=lr, c1=jnp.float32(0.1), c2=jnp.float32(0.05))
    bit_eq(jax.jit(functools.partial(fused_adamw_ref, alpha=alpha))(
               p, g, b, m, v, **aargs),
           jax.jit(lambda *a, **kw: fused_adamw_ref(*a, alpha=al_t, **kw))(
               p, g, b, m, v, **aargs))
    scale = jnp.ones((n // LANE,), jnp.float32)
    bit_eq(jax.jit(functools.partial(fused_lars_ref, alpha=alpha))(
               p, g, b, mom, scale, lr=lr),
           jax.jit(lambda *a, **kw: fused_lars_ref(*a, alpha=al_t, **kw))(
               p, g, b, mom, scale, lr=lr))

    # standalone mix kernel: traced == static bitwise
    ms = jax.jit(functools.partial(gossip_mix_1d, alpha=alpha,
                                   interpret=True))(p, b)
    md = jax.jit(lambda a_, b_: gossip_mix_1d(a_, b_, alpha=al_t,
                                              interpret=True))(p, b)
    np.testing.assert_array_equal(np.asarray(ms, np.float32),
                                  np.asarray(md, np.float32))
    # a zero traced alpha reproduces the statically-dropped partner exactly
    # (the dynamic path keeps the read but the arithmetic must agree)
    z = jax.jit(lambda a_, b_: gossip_mix_1d(a_, b_, alpha=jnp.float32(0.0),
                                             interpret=True))(p, b)
    np.testing.assert_array_equal(np.asarray(z, np.float32),
                                  np.asarray(p, np.float32))

    # Pallas-interpret fused kernels: within 1-2 fp32 ulps of the twins
    # (moments, which see alpha only through tiny weight-decay coupling,
    # come out bit-equal; params absorb the contraction difference)
    tol = dict(rtol=1e-6, atol=1e-7) if dtype == jnp.float32 else \
        dict(rtol=BF16_TOL, atol=BF16_TOL)
    ks = jax.jit(lambda *a, **kw: fused_sgd_1d(
        *a, alpha=al_t, interpret=True, weight_decay=1e-4, **kw))(
        p, g, b, mom, lr=lr)
    rs = jax.jit(lambda *a, **kw: fused_sgd_ref(
        *a, alpha=al_t, weight_decay=1e-4, **kw))(p, g, b, mom, lr=lr)
    for x, y in zip(ks, rs):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), **tol)
    ka = jax.jit(lambda *a, **kw: fused_adamw_1d(
        *a, alpha=al_t, interpret=True, **kw))(p, g, b, m, v, **aargs)
    ra = jax.jit(lambda *a, **kw: fused_adamw_ref(*a, alpha=al_t, **kw))(
        p, g, b, m, v, **aargs)
    for x, y in zip(ka, ra):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ragged_tail(dtype):
    """The sgd/adamw kernels handle non-LANE-multiple buffers: aligned
    prefix through the tiled kernel, < LANE tail through the jnp epilogue —
    together bit-equal to the jnp twin on the whole buffer."""
    from repro.kernels.fused_update import (fused_adamw_1d, fused_adamw_ref,
                                            fused_sgd_1d, fused_sgd_ref)
    rng = np.random.default_rng(3)
    n = 3 * LANE + 37
    p = jnp.asarray(rng.normal(size=(n,)), jnp.float32).astype(dtype)
    g = jnp.asarray(rng.normal(size=(n,)), jnp.float32).astype(dtype)
    b = jnp.asarray(rng.normal(size=(n,)), jnp.float32).astype(dtype)
    mom = jnp.asarray(rng.normal(size=(n,)), jnp.float32).astype(dtype)
    lr = jnp.float32(0.1)
    k = jax.jit(functools.partial(fused_sgd_1d, alpha=0.5, weight_decay=1e-4,
                                  interpret=True))
    r = jax.jit(functools.partial(fused_sgd_ref, alpha=0.5,
                                  weight_decay=1e-4))
    for x, y in zip(k(p, g, b, mom, lr=lr), r(p, g, b, mom, lr=lr)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    ka = jax.jit(functools.partial(fused_adamw_1d, alpha=0.5, interpret=True))
    ra = jax.jit(functools.partial(fused_adamw_ref, alpha=0.5))
    args = dict(lr=lr, c1=jnp.float32(0.1), c2=jnp.float32(0.05))
    for x, y in zip(ka(p, g, b, m, v, **args), ra(p, g, b, m, v, **args)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def _collect_eqns(jaxpr, out, inside_pallas=False):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(("pallas_call", 0))
            continue  # the fused kernel's interior sweep is the point
        sizes = [int(np.prod(v.aval.shape)) for v in eqn.outvars
                 if hasattr(v.aval, "shape")]
        out.append((eqn.primitive.name, max(sizes) if sizes else 0))
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for x in vals:
                if hasattr(x, "eqns"):
                    _collect_eqns(x, out)
                elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                    _collect_eqns(x.jaxpr, out)


def test_fused_step_jaxpr_single_sweep():
    """The fused (pallas-impl) update program contains exactly one fused
    kernel per bucket, NO standalone mix kernel, and no elementwise
    add/mul/sub sweep over full buckets outside the kernels — i.e. the
    single-HBM-pass structure is real, not an accounting claim."""
    from repro.core.gossip import make_packed_fused_update
    from repro.launch.mesh import make_smoke_mesh

    mesh = make_smoke_mesh(1, 1)
    tree = _odd_tree(jnp.float32, lead=(1,))
    layout = build_layout(tree, skip_leading=1)
    opt = sgd(0.1, momentum=0.9, weight_decay=1e-4)
    eng = make_packed_fused_update(mesh, ("data", "model"), None, layout, opt,
                                   alpha=0.0, impl="pallas")
    params = PackedParams.pack(tree, layout)
    grads = jax.tree.map(lambda b: b * 0.1, params)
    state = opt.init(params)
    jaxpr = jax.make_jaxpr(lambda p, g, s: eng(p, g, s))(params, grads, state)
    assert "_mix_kernel" not in str(jaxpr), "standalone mix kernel in step"
    eqns = []
    _collect_eqns(jaxpr.jaxpr, eqns)
    n_pallas = sum(1 for name, _ in eqns if name == "pallas_call")
    assert n_pallas == layout.num_buckets, (n_pallas, layout.num_buckets)
    min_bucket = min(layout.bucket_sizes)
    sweeps = [(n, s) for n, s in eqns
              if n in ("add", "mul", "sub", "div") and s >= min_bucket]
    assert not sweeps, f"optimizer sweeps outside the fused kernel: {sweeps}"

    # the fused lars engine never re-packs the buckets: no bucket-sized
    # concatenate in its jaxpr (the tree-level packed lars pays one concat
    # per bucket per step; the norm prepass's trust-table stack is a
    # handful of scalars, not a repack)
    lopt = lars(0.1, momentum=0.9, weight_decay=1e-4)
    leng = make_packed_fused_update(mesh, ("data", "model"), None, layout,
                                    lopt, alpha=0.0, impl="pallas")
    lstate = lopt.init(params)
    ljaxpr = jax.make_jaxpr(lambda p, g, s: leng(p, g, s))(params, grads,
                                                           lstate)
    leqns = []
    _collect_eqns(ljaxpr.jaxpr, leqns)
    repacks = [(n, s) for n, s in leqns
               if n == "concatenate" and s >= min_bucket]
    assert not repacks, f"fused lars re-packs per step: {repacks}"
    assert "_mix_kernel" not in str(ljaxpr)


def test_fused_bundle_matches_unfused_bundle_dp1():
    """dp=1 smoke: the fused engine must not change the math — losses
    bit-match the unfused packed bundle step for step (the mix is the
    identity at dp=1, so fused == pure optimizer update)."""
    import dataclasses
    from repro.configs import get_config
    from repro.data import ShardedTokenDataset
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.specs import train_input_specs
    from repro.models import reduced
    from repro.train import (Trainer, init_train_state, make_distribution,
                             make_train_step_bundle)

    cfg = dataclasses.replace(reduced(get_config("qwen3-0.6b"), d_model=64),
                              param_dtype="float32", compute_dtype="float32")
    dist = make_distribution(make_smoke_mesh(1, 1), "replica")
    opt = sgd(0.3, momentum=0.9)
    ss, sa, bs = train_input_specs(cfg, dist, 24, 4, opt)
    losses = {}
    for fused in (False, True):
        bundle = make_train_step_bundle(
            cfg, dist, opt, state_shapes=ss, state_axes=sa, batch_shapes=bs,
            protocol="gossip", remat=False, gossip_packed=True,
            fused_update=fused)
        assert bundle.fused == fused
        state, _ = init_train_state(jax.random.key(0), cfg, dist, opt,
                                    packed=True, layout=bundle.layout)
        ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=24, n_shards=1,
                                 batch_per_shard=4, seed=0)
        losses[fused] = [h["loss"] for h in
                         Trainer(bundle, state, ds, log_every=0).run(4)]
    np.testing.assert_array_equal(np.asarray(losses[True]),
                                  np.asarray(losses[False]))


def test_fused_requires_packed_and_backend():
    import dataclasses
    from repro.configs import get_config
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.specs import train_input_specs
    from repro.models import reduced
    from repro.optim import Optimizer
    from repro.train import make_distribution, make_train_step_bundle

    cfg = dataclasses.replace(reduced(get_config("qwen3-0.6b"), d_model=64),
                              param_dtype="float32", compute_dtype="float32")
    dist = make_distribution(make_smoke_mesh(1, 1), "replica")
    opt = sgd(0.3)
    ss, sa, bs = train_input_specs(cfg, dist, 24, 4, opt)
    with pytest.raises(ValueError, match="gossip_packed"):
        make_train_step_bundle(cfg, dist, opt, state_shapes=ss, state_axes=sa,
                               batch_shapes=bs, protocol="gossip",
                               remat=False, fused_update=True)
    bare = Optimizer(opt.init, opt.update)  # no fused backend
    assert bare.fused_update is None
    with pytest.raises(ValueError, match="fused backend"):
        make_train_step_bundle(cfg, dist, bare, state_shapes=ss,
                               state_axes=sa, batch_shapes=bs,
                               protocol="gossip", remat=False,
                               gossip_packed=True, fused_update=True)
    # auto mode silently falls back to the unfused path for bare optimizers
    bundle = make_train_step_bundle(cfg, dist, bare, state_shapes=ss,
                                    state_axes=sa, batch_shapes=bs,
                                    protocol="gossip", remat=False,
                                    gossip_packed=True)
    assert not bundle.fused


# ---------------- p=8 subprocess: engine == unfused composition, all phases

_ENGINE_SCRIPT = r"""
import os, functools
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import (build_schedule, build_layout, PackedParams,
                        exchange_ok, init_inbox_ring,
                        make_packed_fused_update,
                        make_packed_fused_async_update)
from repro.optim import sgd, adamw

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
grads_tree = jax.tree.map(lambda x: x * 0.1 + 0.01, tree)
layout = build_layout(tree, skip_leading=1)

def check(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

OPTS = (("sgd", sgd(0.1, momentum=0.9, weight_decay=1e-4)),
        ("adamw", adamw(0.01, weight_decay=0.02)))

# --- sync engine: fused == [bucket mix ; tree-level update], every phase
for opt_name, opt in OPTS:
    for alpha in (0.0, 0.5):
        for mode in ("static", "dynamic"):
            eng = make_packed_fused_update(mesh, ("data",), sched, layout,
                                           opt, alpha=alpha, mode=mode)
            jeng = [jax.jit(functools.partial(
                        eng, phase=(t if mode == "static" else jnp.int32(t))))
                    for t in range(sched.period + 2)]
            def ref_step(rp, grads, rst, recv_from):
                mixed = PackedParams(
                    [((1.0 - alpha) * b + alpha * b[recv_from]).astype(b.dtype)
                     if alpha else b for b in rp.buckets], layout)
                return opt.update(mixed, grads, rst)
            jref = jax.jit(ref_step)
            params = PackedParams.pack(tree, layout)
            grads = PackedParams.pack(grads_tree, layout)
            st = opt.init(params)
            rp, rst = PackedParams.pack(tree, layout), opt.init(params)
            for t in range(sched.period + 2):
                params, st = jeng[t](params, grads, st)
                rp, rst = jref(rp, grads, rst, jnp.asarray(sched.recv_from(t)))
                for a, b in zip(params.buckets, rp.buckets):
                    check(a, b)
                for k in opt.fused_moments:
                    for a, b in zip(st[k].buckets, rst[k].buckets):
                        check(a, b)
            print(f"ok sync {opt_name} alpha={alpha} mode={mode}")

# --- async engine over the staleness-k ring: the consumed slot is the mix
# operand (masked alpha = alpha * validity); outbox = ppermute(params)
alpha = 0.5
for opt_name, opt in OPTS:
    for k, rate, mode in ((1, 0.0, "static"), (1, 0.0, "dynamic"),
                          (2, 0.35, "static"), (4, 0.0, "static"),
                          (4, 0.35, "dynamic")):
        eng = make_packed_fused_async_update(
            mesh, ("data",), sched, layout, opt, alpha=alpha, staleness=k,
            drop_rate=rate, drop_seed=3, mode=mode)
        jeng = [jax.jit(functools.partial(
                    eng, phase=(t if mode == "static" else jnp.int32(t))))
                for t in range(sched.period + k + 1)]
        def ref_step(rp, grads, ring, rst, recv_from, ok):
            slots, valid, t = ring["slots"], ring["valid"], ring["t"]
            a = alpha * valid[:, 0]
            new_slot = PackedParams([b[recv_from] for b in rp.buckets],
                                    layout)
            mixed = PackedParams(
                [((1.0 - a[:, None, None]) * b
                  + a[:, None, None] * ib).astype(b.dtype)
                 for b, ib in zip(rp.buckets, slots[0].buckets)], layout)
            new_p, new_st = opt.update(mixed, grads, rst)
            new_ring = {"slots": tuple(slots[1:]) + (new_slot,),
                        "valid": jnp.concatenate([valid[:, 1:],
                                                  ok[:, None]], 1),
                        "t": t + 1}
            return new_p, new_st, new_ring
        jref = jax.jit(ref_step)
        params = PackedParams.pack(tree, layout)
        ring = init_inbox_ring(params, k, p)
        grads = PackedParams.pack(grads_tree, layout)
        st = opt.init(params)
        rp = PackedParams.pack(tree, layout)
        rring = init_inbox_ring(rp, k, p)
        rst = opt.init(rp)
        for t in range(sched.period + k + 1):
            params, st, ring = jeng[t](params, grads, ring, st)
            ok = exchange_ok(rring["t"], jnp.arange(p), 3, rate)
            rp, rst, rring = jref(rp, grads, rring, rst,
                                  jnp.asarray(sched.recv_from(t)), ok)
            for a, b in zip(params.buckets, rp.buckets):
                check(a, b)
            check(ring["valid"], rring["valid"])
            for sa, sb in zip(ring["slots"], rring["slots"]):
                for a, b in zip(sa.buckets, sb.buckets):
                    check(a, b)
        print(f"ok async {opt_name} k={k} rate={rate} mode={mode}")

# the fused async engine issues no per-step bucket pack/unpack (the only
# concatenate is the (dp, k) validity-mask roll)
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
jx = jax.make_jaxpr(lambda q, g, b, s: eng(q, g, b, s, jnp.int32(0)))(
    params, grads, ring, st)
eqns = []
collect(jx.jaxpr, eqns)
cats = [(n, s) for n, s in eqns
        if n == "concatenate" and s >= min(layout.bucket_sizes)]
assert not cats, f"fused engine has a per-step bucket concat: {cats}"
print("ok jaxpr no-bucket-concat")

# --- lars sync engine: reference = the REAL tree-level lars applied per
# replica (each rank owns a distinct model — the trust ratio must never
# span replicas).  Pins _lars_row_scale's distributed path.
from repro.optim import lars
lopt = lars(0.1, momentum=0.9, weight_decay=1e-4)
alpha = 0.5
leng = make_packed_fused_update(mesh, ("data",), sched, layout, lopt,
                                alpha=alpha, mode="static")
jleng = [jax.jit(functools.partial(leng, phase=t))
         for t in range(sched.period)]

def lars_ref_step(rp, grads, rst, recv_from):
    mixed = PackedParams(
        [((1.0 - alpha) * b + alpha * b[recv_from]).astype(b.dtype)
         for b in rp.buckets], layout)
    outs = []
    for r in range(p):
        pr = PackedParams([b[r:r + 1] for b in mixed.buckets], layout)
        gr = PackedParams([b[r:r + 1] for b in grads.buckets], layout)
        sr = {"step": rst["step"],
              "mom": PackedParams([b[r:r + 1] for b in rst["mom"].buckets],
                                  layout)}
        outs.append(lopt.update(pr, gr, sr))
    cat = lambda pick: PackedParams(
        [jnp.concatenate([pick(o)[i] for o in outs]) for i in
         range(layout.num_buckets)], layout)
    return (cat(lambda o: o[0].buckets),
            {"step": rst["step"] + 1, "mom": cat(lambda o: o[1]["mom"].buckets)})

jlref = jax.jit(lars_ref_step)
params = PackedParams.pack(tree, layout)
grads = PackedParams.pack(grads_tree, layout)
st = lopt.init(params)
rp, rst = PackedParams.pack(tree, layout), lopt.init(params)
for t in range(sched.period):
    params, st = jleng[t](params, grads, st)
    rp, rst = jlref(rp, grads, rst, jnp.asarray(sched.recv_from(t)))
    for a, b in zip(params.buckets, rp.buckets):
        # <= ~2 fp32 ulps: the trust broadcast (scalar per leaf vs per-row
        # tile) lets XLA pick different FMA contractions
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-7, atol=1e-9)
    for a, b in zip(st["mom"].buckets, rst["mom"].buckets):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-7, atol=1e-9)
print("ok lars per-replica p8")

# --- semantic guard: with lr=0 the fused sync step is the pure mix, whose
# mixing matrix (1-a)I + aP is doubly stochastic — the replica mean of
# every bucket must be invariant across the whole schedule
opt0 = sgd(0.0, momentum=0.0)
eng0 = make_packed_fused_update(mesh, ("data",), sched, layout, opt0,
                                alpha=0.5, mode="static")
params = PackedParams.pack(tree, layout)
st = opt0.init(params)
mean0 = [np.asarray(b).mean(0) for b in params.buckets]
for t in range(2 * sched.period):
    params, st = jax.jit(functools.partial(eng0, phase=t))(params, grads, st)
for b, m0 in zip(params.buckets, mean0):
    np.testing.assert_allclose(np.asarray(b).mean(0), m0,
                               rtol=1e-5, atol=1e-6)
print("ok mean preservation")
print("ALL_OK")
"""


@pytest.mark.slow
def test_fused_engine_matches_unfused_p8():
    """Acceptance: fused vs unfused updates bit-identical in fp32 across
    all schedule phases at p=8 — sync and async engines, sgd and adamw,
    alpha in {0, 0.5}, static and dynamic phase selection.

    'Unfused' here is the unfused mix-then-apply COMPOSITION of the fused
    step's own algebra: the genuine tree-level ``optimizer.update`` after a
    standalone bucket mix, with the ppermute modeled as the simulator's
    gather.  It is deliberately NOT the dp>1 unfused train step, which
    implements a different (PR-1/2) algebra — the fused default shifts the
    partner term one update staler by design; that semantic change is
    documented in train/step.py and guarded here by (a) a per-replica
    tree-level LARS reference (pinning the norm-prepass distributed path)
    and (b) a doubly-stochastic mean-preservation invariant at lr=0."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", _ENGINE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL_OK" in r.stdout
