"""Mamba SSM unit tests: scan equivalences, decode==train, conv state."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.models.config import SSMSpec
from repro.models.mamba import (mamba_apply, mamba_decode, mamba_init,
                                mamba_state_init, ssm_assoc_scan, ssm_scan_ref)


def test_assoc_scan_matches_sequential():
    B, S, D, N = 2, 33, 5, 4
    key = jax.random.key(0)
    dA = jax.random.uniform(key, (B, S, D, N), minval=0.3, maxval=0.99)
    dBx = jax.random.normal(jax.random.key(1), (B, S, D, N))
    np.testing.assert_allclose(np.asarray(ssm_assoc_scan(dA, dBx)),
                               np.asarray(ssm_scan_ref(dA, dBx)),
                               rtol=1e-5, atol=1e-5)


@given(st.sampled_from([1, 2]), st.sampled_from([1, 7, 33]), st.sampled_from([1, 5]),
       st.sampled_from([1, 4]))
@settings(max_examples=8, deadline=None)
def test_assoc_scan_property(B, S, D, N):
    key = jax.random.key(S * 7 + D)
    dA = jax.random.uniform(key, (B, S, D, N), minval=0.0, maxval=1.0)
    dBx = jax.random.normal(jax.random.fold_in(key, 1), (B, S, D, N))
    np.testing.assert_allclose(np.asarray(ssm_assoc_scan(dA, dBx)),
                               np.asarray(ssm_scan_ref(dA, dBx)),
                               rtol=2e-5, atol=2e-5)


def test_mamba_decode_matches_full():
    d_model = 32
    spec = SSMSpec(d_state=8, d_conv=4, expand=2)
    p, _ = mamba_init(jax.random.key(0), d_model, spec, jnp.float32)
    S = 11
    x = jax.random.normal(jax.random.key(1), (2, S, d_model)) * 0.3
    full = mamba_apply(p, spec, d_model, x)
    state = mamba_state_init(spec, d_model, 2, jnp.float32)
    outs = []
    for t in range(S):
        y, state = mamba_decode(p, spec, d_model, x[:, t:t + 1], state)
        outs.append(y)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=5e-4, atol=5e-4)


def test_mamba_state_is_constant_size():
    """The O(1)-state property that makes long_500k decode trivial."""
    spec = SSMSpec(d_state=8, d_conv=4, expand=2)
    s = mamba_state_init(spec, 64, 3, jnp.float32)
    assert s["h"].shape == (3, 128, 8)
    assert s["conv"].shape == (3, 3, 128)


def test_mamba_custom_scan_impl_hook():
    """scan_impl injection (used to swap in the Pallas kernel) is honored."""
    d_model = 16
    spec = SSMSpec(d_state=4, d_conv=4, expand=2)
    p, _ = mamba_init(jax.random.key(0), d_model, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 8, d_model)) * 0.3
    called = {}

    def my_scan(dA, dBx):
        called["yes"] = True
        return ssm_scan_ref(dA, dBx)

    out = mamba_apply(p, spec, d_model, x, scan_impl=my_scan)
    assert called.get("yes")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(mamba_apply(p, spec, d_model, x)),
                               rtol=1e-5, atol=1e-5)
