"""Compile the main path's kernels for a described TPU v5e, no chip needed.

Interpret mode never checks Mosaic's tiling rules or VMEM budget; these
compiles do, at the real bucket size (``DEFAULT_BUCKET_BYTES``) and the
benchmark cell's attention shape, and assert that each kernel lowered to a
Mosaic ``tpu_custom_call``. Nothing runs, so they say nothing about results
or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import importlib.util
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.buckets import DEFAULT_BUCKET_BYTES
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_update import fused_adamw_1d, fused_sgd_1d
from repro.kernels.gossip_mix import LANE, gossip_mix_2d, gossip_mix_q2d
from repro.kernels.quantize import encode_wire
from repro.launch.mesh import make_mesh
from repro.launch.specs import train_input_specs
from repro.models.config import reduced
from repro.optim import sgd
from repro.train.sharding import make_distribution
from repro.train.step import init_train_state, make_train_step_bundle

BF16_N = DEFAULT_BUCKET_BYTES // 2      # elements of one bf16 bucket
F32_N = DEFAULT_BUCKET_BYTES // 4
FP8 = jnp.float8_e4m3fn


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, args, donate=()):
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _update_kernel_pattern():
    """The benchmark's update reader's pattern for the fused kernel's ops."""
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench" / "metrics"
            / "update_roofline.py")
    spec = importlib.util.spec_from_file_location("update_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KERNEL


@pytest.mark.parametrize("dtype,n", [(jnp.bfloat16, BF16_N),
                                     (jnp.float32, F32_N)])
@pytest.mark.parametrize("alpha", [0.0, 0.5, "traced"])
def test_fused_sgd_compiles(one_chip, dtype, n, alpha):
    s = lambda dt: _shape(one_chip, (1, n), dt)
    args = [s(dtype), s(dtype), s(dtype), s(dtype)]
    if alpha == "traced":
        args.append(_shape(one_chip, (), jnp.float32))
        fn = lambda p, g, b, m, a: fused_sgd_1d(p, g, b, m, lr=0.1, alpha=a,
                                                donate=True)
    else:
        fn = lambda p, g, b, m: fused_sgd_1d(p, g, b, m, lr=0.1, alpha=alpha,
                                             donate=True)
    assert "tpu_custom_call" in _compile_text(fn, args, donate=(0, 3))


@pytest.mark.parametrize("dtype,n", [(jnp.bfloat16, BF16_N),
                                     (jnp.float32, F32_N)])
def test_fused_adamw_compiles(one_chip, dtype, n):
    s = lambda dt: _shape(one_chip, (1, n), dt)
    fn = lambda p, g, b, m, v: fused_adamw_1d(p, g, b, m, v, lr=0.1, c1=0.1,
                                              c2=0.1, alpha=0.5, donate=True)
    args = [s(dtype), s(dtype), s(dtype), s(jnp.float32), s(jnp.float32)]
    assert "tpu_custom_call" in _compile_text(fn, args, donate=(0, 3, 4))


@pytest.mark.parametrize("dtype,n", [(jnp.bfloat16, BF16_N),
                                     (jnp.float32, F32_N)])
def test_gossip_mix_2d_compiles(one_chip, dtype, n):
    s = _shape(one_chip, (n // LANE, LANE), dtype)
    fn = lambda a, b: gossip_mix_2d(a, b, alpha=0.5, donate=True)
    assert "tpu_custom_call" in _compile_text(fn, [s, s], donate=(0,))


@pytest.mark.parametrize("code_dtype", [jnp.int8, FP8])
def test_gossip_mix_q2d_compiles(one_chip, code_dtype):
    rows = BF16_N // LANE
    args = [_shape(one_chip, (rows, LANE), jnp.bfloat16),
            _shape(one_chip, (rows, LANE), code_dtype),
            _shape(one_chip, (rows,), jnp.float32)]
    fn = lambda a, q, sc: gossip_mix_q2d(a, q, sc, alpha=0.5, donate=True)
    assert "tpu_custom_call" in _compile_text(fn, args, donate=(0,))


@pytest.mark.parametrize("code_dtype", [jnp.int8, FP8])
def test_fused_sgd_quantized_partner_compiles(one_chip, code_dtype):
    s = lambda dt: _shape(one_chip, (1, BF16_N), dt)
    args = [s(jnp.bfloat16), s(jnp.bfloat16), s(code_dtype),
            _shape(one_chip, (BF16_N // LANE,), jnp.float32), s(jnp.bfloat16)]
    fn = lambda p, g, q, sc, m: fused_sgd_1d(p, g, q, m, lr=0.1, alpha=0.5,
                                             partner_scales=sc, donate=True)
    assert "tpu_custom_call" in _compile_text(fn, args, donate=(0, 4))


def test_fp8_wire_encode_compiles(one_chip):
    """v5e has no fp8 hardware; the e4m3 encode must still compile."""
    fn = lambda x: encode_wire(x, "fp8")
    _compile_text(fn, [_shape(one_chip, (1, BF16_N), jnp.bfloat16)])


def test_kernels_carry_their_names(one_chip):
    """Each Mosaic call is named after its kernel, so a trace finds the
    fused update by name: the update reader's pattern matches its calls and
    not the gossip mixes'."""
    kernel = _update_kernel_pattern()
    n = 8 * LANE + 5                      # an aligned body and a ragged tail
    s = lambda dt: _shape(one_chip, (1, n), dt)
    fused = _compile_text(
        lambda p, g, b, m: fused_sgd_1d(p, g, b, m, lr=0.1, alpha=0.5),
        [s(jnp.bfloat16)] * 4)
    m = _shape(one_chip, (8, LANE), jnp.bfloat16)
    mix = _compile_text(lambda a, b: gossip_mix_2d(a, b, alpha=0.5), [m, m])
    q = [m, _shape(one_chip, (8, LANE), jnp.int8),
         _shape(one_chip, (8,), jnp.float32)]
    wire = _compile_text(
        lambda a, c, sc: gossip_mix_q2d(a, c, sc, alpha=0.5), q)
    calls = lambda text: [line.strip().removeprefix("ROOT ")
                          for line in text.splitlines()
                          if "custom-call(" in line]
    assert calls(fused) and all(c.startswith("%fused_update")
                                and kernel.match(c) for c in calls(fused))
    for text, name in ((mix, "%gossip_mix."), (wire, "%gossip_mix_wire.")):
        assert calls(text) and all(c.startswith(name) and not kernel.match(c)
                                   for c in calls(text))


def _custom_calls(text):
    return [line.strip().removeprefix("ROOT ") for line in text.splitlines()
            if "custom-call(" in line]


def _flash_kernels(text):
    """The flash kernels' names among the Mosaic calls: fwd, dkv, dq."""
    names = set()
    for call in _custom_calls(text):
        m = re.match(r"%\S*?flash_attention(_dkv|_dq)?[_.]", call)
        if m:
            names.add("flash_attention" + (m.group(1) or ""))
    return names


def test_flash_attention_fwd_bwd_compiles(one_chip):
    """The train path's attention at the cell's shape (B 4, S 1024, 16
    query and 8 KV heads of 128, bf16): forward, dK/dV and dQ lower to
    Mosaic calls within the VMEM budget, none matching the update reader."""
    kernel = _update_kernel_pattern()
    q = _shape(one_chip, (4, 16, 1024, 128), jnp.bfloat16)
    kv = _shape(one_chip, (4, 8, 1024, 128), jnp.bfloat16)
    loss = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v).astype(jnp.float32))
    text = _compile_text(jax.grad(loss, (0, 1, 2)), [q, kv, kv])
    assert _flash_kernels(text) == {"flash_attention", "flash_attention_dkv",
                                    "flash_attention_dq"}
    assert not any(kernel.match(c) for c in _custom_calls(text))


def test_step_takes_flash_per_replica_on_four_chips(topo):
    """The gossip step of a two-layer qwen3 at data=4 on a described
    v5e:2x2: every attention site takes the kernels, and no all-gather
    feeds them (each replica's call runs on its own chip)."""
    cfg = reduced(get_config("qwen3-0.6b"), d_model=512)
    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices[:4])
    dist = make_distribution(mesh, cfg.dist_mode)
    opt = sgd(0.01, momentum=0.9)
    shapes, axes, batch = train_input_specs(cfg, dist, 256, 4, opt)
    bundle = make_train_step_bundle(
        cfg, dist, opt, state_shapes=shapes, state_axes=axes,
        batch_shapes=batch, protocol="gossip", gossip_packed=True,
        remat=True)
    state = jax.eval_shape(lambda k: init_train_state(
        k, cfg, dist, opt, packed=True, layout=bundle.layout)[0],
        jax.random.key(0))
    place = lambda tree, shard: jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shard)
    text = bundle.jitted(0).lower(
        place(state, bundle.state_shardings),
        place(batch, bundle.batch_shardings)).compile().as_text()
    assert bundle.attn_paths == {"flash": 1, "dense": 0}
    assert _flash_kernels(text) == {"flash_attention", "flash_attention_dkv",
                                    "flash_attention_dq"}
    assert "all-gather" not in text
    assert "collective-permute" in text
