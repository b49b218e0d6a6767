"""Optimizers, schedules, data pipeline, checkpoint roundtrip."""
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import restore_state, save_state
from repro.core import RingShardRotation
from repro.data import BigramTaskDataset, ShardedTokenDataset, make_replica_batches
from repro.optim import adamw, constant, cosine_warmup, scale_lr_sqrt_p, sgd, step_decay


# ---------------------------------------------------------------- optim
def test_sgd_momentum_manual():
    opt = sgd(0.1, momentum=0.9)
    p = {"w": jnp.array([1.0, 2.0])}
    s = opt.init(p)
    g = {"w": jnp.array([0.5, -0.5])}
    p1, s1 = opt.update(p, g, s)
    np.testing.assert_allclose(np.asarray(p1["w"]), [1 - 0.05, 2 + 0.05])
    p2, s2 = opt.update(p1, g, s1)
    # momentum: m2 = 0.9*0.5 + 0.5 = 0.95
    np.testing.assert_allclose(np.asarray(p2["w"]),
                               np.asarray(p1["w"]) - 0.1 * np.array([0.95, -0.95]),
                               rtol=1e-6)


def test_sgd_weight_decay():
    opt = sgd(0.1, momentum=0.0, weight_decay=0.1)
    p = {"w": jnp.array([1.0])}
    s = opt.init(p)
    p1, _ = opt.update(p, {"w": jnp.array([0.0])}, s)
    np.testing.assert_allclose(np.asarray(p1["w"]), [1.0 - 0.1 * 0.1])


def test_adamw_first_step_unit():
    opt = adamw(1e-2, b1=0.9, b2=0.999)
    p = {"w": jnp.array([0.0])}
    s = opt.init(p)
    p1, _ = opt.update(p, {"w": jnp.array([3.0])}, s)
    # bias-corrected first step == -lr * sign(g)
    np.testing.assert_allclose(np.asarray(p1["w"]), [-1e-2], rtol=1e-4)


def test_step_decay_matches_paper_regimen():
    """ResNet-50 regimen: x0.1 every 30 (epochs)."""
    f = step_decay(0.1, 0.1, 30)
    assert float(f(0)) == pytest.approx(0.1)
    assert float(f(29)) == pytest.approx(0.1)
    assert float(f(30)) == pytest.approx(0.01)
    assert float(f(90)) == pytest.approx(1e-4)


def test_sqrt_p_scaling():
    f = scale_lr_sqrt_p(constant(0.1), 16)
    assert float(f(0)) == pytest.approx(0.4)


def test_cosine_warmup_shape():
    f = cosine_warmup(1.0, warmup=10, total=100)
    assert float(f(0)) == 0.0
    assert float(f(10)) == pytest.approx(1.0, rel=1e-3)
    assert float(f(100)) == pytest.approx(0.1, rel=1e-2)


# ---------------------------------------------------------------- data
def test_dataset_deterministic():
    ds = ShardedTokenDataset(vocab=64, seq_len=8, n_shards=4, batch_per_shard=2)
    a = ds.rank_batch(1, 5)
    b = ds.rank_batch(1, 5)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (2, 9)
    assert a.min() >= 0 and a.max() < 64


def test_ring_rotation_revisit_property():
    """§4.5.2: a shard returns to its origin rank only after every other rank
    consumed it once."""
    p = 6
    rot = RingShardRotation(p)
    for rank in range(p):
        seen = [rot.shard_for_rank(rank, t) for t in range(p)]
        assert sorted(seen) == list(range(p))       # all shards exactly once
        assert rot.shard_for_rank(rank, p) == seen[0]  # returns after p steps


def test_rotation_assignment_is_permutation():
    rot = RingShardRotation(8)
    for t in range(9):
        assert sorted(rot.assignment(t)) == list(range(8))


def test_replica_batches_shape():
    ds = ShardedTokenDataset(vocab=64, seq_len=8, n_shards=4, batch_per_shard=2)
    b = make_replica_batches(ds, 0, 4)
    assert b["tokens"].shape == (4, 2, 9)


def test_bigram_task_is_learnable():
    """The bigram oracle assigns much lower CE than uniform — so convergence
    curves in the benches have real signal."""
    task = BigramTaskDataset(vocab=32, seed=0)
    rng = np.random.default_rng(1)
    toks = task.sample(rng, 16, 64)
    # oracle CE: -log p(next | cur) under the true transition table
    ce, n = 0.0, 0
    for row in toks:
        for t in range(len(row) - 1):
            cur, nxt = row[t], row[t + 1]
            cand = task.next_tok[cur]
            pr = task.next_p[cur][cand == nxt].sum()
            ce -= math.log(max(pr, 1e-9))
            n += 1
    ce /= n
    assert ce < math.log(32) * 0.8


# ---------------------------------------------------------------- ckpt
def test_checkpoint_roundtrip(tmp_path):
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3),
                        "b": jnp.ones((4,), jnp.bfloat16)},
             "opt": {"step": jnp.int32(7), "mom": None}}
    path = os.path.join(tmp_path, "ckpt")
    save_state(path, state, metadata={"arch": "test"}, step=7)
    tmpl = jax.tree.map(jnp.zeros_like, state)
    restored, manifest = restore_state(path, tmpl)
    assert manifest["metadata"]["arch"] == "test"
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(state["params"]["w"]))
    assert restored["params"]["b"].dtype == jnp.bfloat16
    assert int(restored["opt"]["step"]) == 7


def test_checkpoint_structure_mismatch_raises(tmp_path):
    state = {"a": jnp.zeros(3)}
    path = os.path.join(tmp_path, "ckpt")
    save_state(path, state)
    with pytest.raises(ValueError):
        restore_state(path, {"b": jnp.zeros(3)})


def test_lars_trust_ratio_scaling():
    from repro.optim import lars
    opt = lars(1.0, momentum=0.0, trust_coef=1e-3)
    p = {"w": jnp.full((4,), 2.0)}
    s = opt.init(p)
    g = {"w": jnp.full((4,), 1.0)}
    p1, _ = opt.update(p, g, s)
    # trust = 1e-3 * ||w||/||g|| = 1e-3 * 2 -> step = lr * trust * g
    np.testing.assert_allclose(np.asarray(p1["w"]), 2.0 - 2e-3, rtol=1e-5)


def test_lars_zero_grad_no_nan():
    from repro.optim import lars
    opt = lars(0.1)
    p = {"w": jnp.ones((3,))}
    s = opt.init(p)
    p1, _ = opt.update(p, {"w": jnp.zeros((3,))}, s)
    assert bool(jnp.isfinite(p1["w"]).all())
