"""MoE dispatch/combine unit + property tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.models.config import MoESpec
from repro.models.layers import silu
from repro.models.moe import moe_apply, moe_capacity, moe_init


def _dense_oracle(p, spec, x):
    """Route every token through its top-k experts WITHOUT capacity limits."""
    B, S, d = x.shape
    logits = (x @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    topw, topi = jax.lax.top_k(probs, spec.top_k)
    if spec.router_scale:
        topw = topw / (topw.sum(-1, keepdims=True) + 1e-9)
    # compute all experts densely, then select
    h = silu(jnp.einsum("bsd,edf->bsef", x, p["w_gate"])) * \
        jnp.einsum("bsd,edf->bsef", x, p["w_in"])
    ye = jnp.einsum("bsef,efd->bsed", h, p["w_out"])        # (B,S,E,d)
    sel = jnp.take_along_axis(ye, topi[..., None], axis=2)  # (B,S,k,d)
    out = (sel * topw[..., None].astype(sel.dtype)).sum(2)
    if spec.n_shared:
        from repro.models.layers import mlp_apply
        out = out + mlp_apply(p["shared"], x, "swiglu")
    return out


def test_moe_matches_dense_oracle_when_capacity_suffices():
    spec = MoESpec(n_experts=4, top_k=2, d_ff_expert=32, capacity_factor=8.0)
    d = 16
    p, _ = moe_init(jax.random.key(0), d, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 12, d)) * 0.5
    y, m = moe_apply(p, spec, x)
    want = _dense_oracle(p, spec, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert float(m["moe_dropped_frac"]) == 0.0


def test_moe_shared_expert():
    spec = MoESpec(n_experts=4, top_k=2, d_ff_expert=32, n_shared=1,
                   capacity_factor=8.0)
    d = 16
    p, _ = moe_init(jax.random.key(0), d, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 8, d)) * 0.5
    y, _ = moe_apply(p, spec, x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_dense_oracle(p, spec, x)),
                               rtol=2e-4, atol=2e-4)


def test_moe_capacity_drops_reported():
    """With capacity_factor << 1, tokens must drop and be reported."""
    spec = MoESpec(n_experts=4, top_k=2, d_ff_expert=16, capacity_factor=0.25)
    d = 8
    p, _ = moe_init(jax.random.key(0), d, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 32, d))
    y, m = moe_apply(p, spec, x)
    assert float(m["moe_dropped_frac"]) > 0.0
    assert bool(jnp.isfinite(y).all())


def test_aux_loss_minimal_when_balanced():
    """Perfectly uniform router -> aux loss == aux_coef (the minimum of
    E * sum f_e P_e is 1 at uniform load)."""
    spec = MoESpec(n_experts=4, top_k=1, d_ff_expert=8, aux_coef=1.0)
    d = 8
    p, _ = moe_init(jax.random.key(0), d, spec, jnp.float32)
    p = dict(p)
    p["router"] = jnp.zeros_like(p["router"])  # uniform probs
    x = jax.random.normal(jax.random.key(1), (1, 64, d))
    _, m = moe_apply(p, spec, x)
    # f_e from top-1 of uniform probs is tie-broken deterministically, but
    # P_e is exactly 1/E, so aux = E * sum_e f_e / E = 1
    np.testing.assert_allclose(float(m["moe_aux"]), 1.0, rtol=1e-5)


@given(st.sampled_from([2, 4, 8]), st.sampled_from([1, 2, 4]), st.sampled_from([4, 16]))
@settings(max_examples=8, deadline=None)
def test_moe_finite_and_shape(E, k, S):
    k = min(k, E)
    spec = MoESpec(n_experts=E, top_k=k, d_ff_expert=8, capacity_factor=1.25)
    d = 8
    p, _ = moe_init(jax.random.key(E * 10 + k), d, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(S), (2, S, d))
    y, m = moe_apply(p, spec, x)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())
    assert 0.0 <= float(m["moe_dropped_frac"]) <= 1.0


def test_capacity_formula():
    spec = MoESpec(n_experts=8, top_k=2, d_ff_expert=8, capacity_factor=1.0)
    assert moe_capacity(32, spec) == 8
    assert moe_capacity(1, spec) == 1
