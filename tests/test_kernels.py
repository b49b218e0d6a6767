"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU), with
hypothesis shape/dtype sweeps as required per kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import flash_mha, gossip_mix_flat, ssm_scan
from repro.kernels.ref import attention_ref, gossip_mix_ref, ssm_scan_ref

DTYPES = [jnp.float32, jnp.bfloat16]


# ------------------------------------------------------------- gossip_mix
@given(st.integers(1, 5000), st.sampled_from([0, 1]),
       st.floats(0.0, 1.0))
@settings(max_examples=25, deadline=None)
def test_gossip_mix_sweep(n, dti, alpha):
    dtype = DTYPES[dti]
    key = jax.random.key(n)
    a = jax.random.normal(key, (n,), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32).astype(dtype)
    got = gossip_mix_flat(a, b, alpha=alpha)
    want = gossip_mix_ref(a, b, alpha)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_gossip_mix_multidim():
    a = jax.random.normal(jax.random.key(0), (3, 7, 11))
    b = jax.random.normal(jax.random.key(1), (3, 7, 11))
    np.testing.assert_allclose(np.asarray(gossip_mix_flat(a, b)),
                               np.asarray(gossip_mix_ref(a, b)), rtol=1e-6)


def test_gossip_mix_half_alpha_is_paper_average():
    a = jnp.full((256,), 2.0)
    b = jnp.full((256,), 4.0)
    np.testing.assert_allclose(np.asarray(gossip_mix_flat(a, b)), 3.0)


# ------------------------------------------------------------- ssm_scan
@given(st.integers(1, 2), st.integers(1, 80), st.integers(1, 20),
       st.integers(1, 8), st.sampled_from([16, 32]), st.sampled_from([8, 16]))
@settings(max_examples=15, deadline=None)
def test_ssm_scan_sweep(B, S, D, N, chunk, block_d):
    key = jax.random.key(S * 131 + D)
    dA = jax.random.uniform(key, (B, S, D, N), minval=0.2, maxval=1.0)
    dBx = jax.random.normal(jax.random.fold_in(key, 1), (B, S, D, N))
    got = ssm_scan(dA, dBx, chunk=chunk, block_d=block_d)
    want = ssm_scan_ref(dA, dBx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ssm_scan_chunk_boundaries_exact():
    """State carried across chunk boundaries must be exact: compare a run
    whose S spans multiple chunks against the scan oracle."""
    B, S, D, N = 1, 256, 8, 4
    key = jax.random.key(0)
    dA = jax.random.uniform(key, (B, S, D, N), minval=0.9, maxval=1.0)
    dBx = jax.random.normal(jax.random.fold_in(key, 1), (B, S, D, N)) * 0.1
    got = ssm_scan(dA, dBx, chunk=64, block_d=8)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ssm_scan_ref(dA, dBx)),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------- flash attn
@pytest.mark.parametrize("window", [None, 32])
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention_basic(window, dtype):
    B, H, S, d = 1, 2, 128, 32
    key = jax.random.key(0)
    q = (jax.random.normal(key, (B, H, S, d)) * 0.3).astype(dtype)
    k = (jax.random.normal(jax.random.fold_in(key, 1), (B, H, S, d)) * 0.3).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, d)).astype(dtype)
    got = flash_mha(q, k, v, causal=True, window=window, block_q=32, block_k=32)
    want = attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@given(st.sampled_from([32, 64, 128]), st.sampled_from([32, 64]),
       st.sampled_from([16, 64]), st.booleans())
@settings(max_examples=10, deadline=None)
def test_flash_attention_sweep(S, bq, d, causal):
    B, H = 1, 1
    key = jax.random.key(S + d)
    q = jax.random.normal(key, (B, H, S, d)) * 0.2
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, S, d)) * 0.2
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, d))
    got = flash_mha(q, k, v, causal=causal, block_q=bq, block_k=bq)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_flash_cross_shaped_kv():
    """T != S (e.g. scoring a prompt against a longer memory)."""
    B, H, S, T, d = 1, 2, 64, 128, 32
    key = jax.random.key(0)
    q = jax.random.normal(key, (B, H, S, d)) * 0.2
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, T, d)) * 0.2
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, T, d))
    got = flash_mha(q, k, v, causal=False, block_q=32, block_k=32)
    want = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
