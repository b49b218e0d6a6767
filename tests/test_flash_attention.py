"""The flash kernels' forward and backward against ``_sdpa`` and its
autodiff, in interpret mode at small shapes, and the attention module's
dispatch to them. Blocks of 32-128 walked in 32-square tiles over 128
tokens give every kind of block and tile: skipped, crossed by the diagonal
or the window's edge, and whole."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_blocks
from repro.models.attention import (_dense_attn, _flash_attn, _sdpa,
                                    attn_apply, attn_init, causal_window_mask,
                                    count_attn_paths)
from repro.models.config import AttnSpec


def _heads_last(x):
    return jnp.swapaxes(x, -3, -2)


def _dense(q, k, v, window):
    """_sdpa on heads-major q (..., H, S, d) and k/v (..., K, T, d)."""
    mask = causal_window_mask(q.shape[-2], k.shape[-2], window)
    return _heads_last(_sdpa(_heads_last(q), _heads_last(k), _heads_last(v),
                             mask, k.shape[-3]))


def _inputs(shape_q, shape_kv, dtype, seed=0):
    key = jax.random.key(seed)
    q, k, v, g = (jax.random.normal(jax.random.fold_in(key, i), s)
                  for i, s in enumerate((shape_q, shape_kv, shape_kv,
                                         shape_q)))
    return tuple(x.astype(dtype) for x in (q, k, v)), g


def _loss(attend, g):
    return lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * g)


def _assert_close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max())


@pytest.mark.parametrize("H,K,d,window,dtype,vmapped,blocks", [
    (4, 2, 128, None, jnp.float32, True, (64, 128, 32)),  # GQA, replica vmap
    (2, 2, 64, 48, jnp.bfloat16, False, (32, 64, 32)),    # MHA, window, bf16
])
def test_flash_matches_sdpa_and_its_grad(H, K, d, window, dtype, vmapped,
                                         blocks):
    B, S = 1, 128
    lead = (2,) if vmapped else ()
    (q, k, v), g = _inputs(lead + (B, H, S, d), lead + (B, K, S, d), dtype)
    bq, bk, sub = blocks
    flash = lambda q, k, v: flash_attention(q, k, v, window=window,
                                            block_q=bq, block_k=bk,
                                            block_sub=sub, interpret=True)
    dense = lambda q, k, v: _dense(q, k, v, window)
    if vmapped:
        flash, dense = jax.vmap(flash), jax.vmap(dense)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    _assert_close(jax.jit(flash)(q, k, v), dense(q, k, v), tol)
    got = jax.jit(jax.grad(_loss(flash, g), (0, 1, 2)))(q, k, v)
    want = jax.grad(_loss(dense, g), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        _assert_close(a, b, tol)


def test_attn_flash_path_matches_dense_path():
    """The heads-major projections, qk-norm, partial rotary and GQA of the
    flash branch give the dense branch's output and parameter gradients."""
    spec = AttnSpec(n_heads=4, n_kv_heads=2, head_dim=64, qk_norm=True,
                    rope_frac=0.5, window=80)
    d_model, S = 64, 128
    p, _ = attn_init(jax.random.key(0), d_model, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, S, d_model)) * 0.5
    pos = jnp.arange(S)[None]
    flash = lambda p: _flash_attn(p, spec, x, pos, (64, 128), interpret=True)
    dense = lambda p: _dense_attn(p, spec, x, x, pos, pos)
    _assert_close(jax.jit(flash)(p), jax.jit(dense)(p), 2e-5)
    sq = lambda f: lambda p: jnp.sum(f(p) ** 2)
    got, want = (jax.jit(jax.grad(sq(f)))(p) for f in (flash, dense))
    for name in p:
        _assert_close(got[name], want[name], 5e-5)


def test_attn_paths_counts_sites_by_path():
    """Causal self-attention that the blocks tile counts as flash; a length
    they cannot tile, non-causal and cross-attention count as dense. Off the
    TPU every site still computes ``_sdpa``'s result."""
    causal = AttnSpec(n_heads=2, n_kv_heads=1, head_dim=64)
    cross = AttnSpec(n_heads=2, n_kv_heads=2, head_dim=64, causal=False,
                     cross=True)
    enc = AttnSpec(n_heads=2, n_kv_heads=2, head_dim=64, causal=False)
    p, _ = attn_init(jax.random.key(0), 64, causal, jnp.float32)
    pc, _ = attn_init(jax.random.key(1), 64, cross, jnp.float32)
    x = jax.random.normal(jax.random.key(2), (1, 128, 64))
    with count_attn_paths() as paths:
        out = jax.jit(attn_apply, static_argnums=1)(p, causal, x)
        jax.eval_shape(lambda: (attn_apply(p, causal, x[:, :100]),
                                attn_apply(pc, cross, x, memory=x),
                                attn_apply(pc, enc, x)))
    assert paths == {"flash": 1, "dense": 3}
    pos = jnp.arange(128)[None]
    _assert_close(out, _dense_attn(p, causal, x, x, pos, pos), 1e-6)


def test_flash_blocks_follow_the_shapes():
    assert flash_blocks(1024, 1024, 128) == (1024, 1024)
    assert flash_blocks(4096, 4096, 64) == (1024, 1024)
    assert flash_blocks(384, 384, 64) == (128, 128)
    assert flash_blocks(1024, 1024, 256) == (512, 512)
    assert flash_blocks(100, 100, 128) is None
