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
import math
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.ops as kernel_ops
from repro.configs import get_config
from repro.core.buckets import DEFAULT_BUCKET_BYTES
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_update import fused_adamw_1d, fused_sgd_1d
from repro.kernels.grouped_matmul import grouped_matmul
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
    s = lambda dt: _shape(one_chip, (1, n // LANE, LANE), dt)
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
    s = lambda dt: _shape(one_chip, (1, n // LANE, LANE), dt)
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
    s = lambda dt: _shape(one_chip, (1, BF16_N // LANE, LANE), dt)
    args = [s(jnp.bfloat16), s(jnp.bfloat16), s(code_dtype),
            _shape(one_chip, (1, BF16_N // LANE), jnp.float32),
            s(jnp.bfloat16)]
    fn = lambda p, g, q, sc, m: fused_sgd_1d(p, g, q, m, lr=0.1, alpha=0.5,
                                             partner_scales=sc, donate=True)
    assert "tpu_custom_call" in _compile_text(fn, args, donate=(0, 4))


def test_fp8_wire_encode_compiles(one_chip):
    """v5e has no fp8 hardware; the e4m3 encode must still compile."""
    fn = lambda x: encode_wire(x, "fp8")
    _compile_text(fn, [_shape(one_chip, (1, BF16_N // LANE, LANE),
                              jnp.bfloat16)])


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


def test_flash_attention_at_latent_widths_compiles(one_chip):
    """DeepSeek-V2-Lite's attention at its cell's shape (B 4, S 4096, 16
    heads, q/k 192 = nope 128 + rope 64, v 128, bf16, 512-square blocks):
    the three kernels take a v narrower than q/k."""
    q = _shape(one_chip, (4, 16, 4096, 192), jnp.bfloat16)
    v = _shape(one_chip, (4, 16, 4096, 128), jnp.bfloat16)
    loss = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, scale=0.1147).astype(jnp.float32))
    text = _compile_text(jax.grad(loss, (0, 1, 2)), [q, q, v])
    assert _flash_kernels(text) == {"flash_attention", "flash_attention_dkv",
                                    "flash_attention_dq"}


def test_grouped_matmul_fwd_bwd_compiles(one_chip):
    """The held experts' SwiGLU at the cell's shape (the C = 12288-row
    buffer, d 2048, expert width 1408, 8 experts, bf16): forward and both
    backward products lower to Mosaic calls named ``expert_gmm`` and
    ``expert_tgmm``, each group's whole weight block within the VMEM
    limit, and every call on the C-row buffer."""
    C, d, f, E = 12288, 2048, 1408, 8
    w = lambda *s: _shape(one_chip, s, jnp.bfloat16)

    def loss(x, wg, wi, wo, sizes):
        h = jax.nn.silu(grouped_matmul(x, wg, sizes)) * grouped_matmul(
            x, wi, sizes)
        return jnp.sum(grouped_matmul(h, wo, sizes).astype(jnp.float32))

    text = _compile_text(jax.grad(loss, (0, 1, 2, 3)),
                         [w(C, d), w(E, d, f), w(E, d, f), w(E, f, d),
                          _shape(one_chip, (E,), jnp.int32)])
    calls = [c for c in _custom_calls(text) if "expert_" in c.split(" = ")[0]]
    names = {re.match(r"%\S*?(expert_t?gmm)", c).group(1) for c in calls}
    # 2 forward products (the third's output is dead under grad), 3 with
    # the weights transposed and 3 weight gradients
    assert names == {"expert_gmm", "expert_tgmm"} and len(calls) == 8
    for c in calls:
        assert re.search(rf"\[(12288,\d+|{E},\d+,\d+)\]", c), c


def _packed_gossip_step(topo, dp, monkeypatch=None):
    """(bundle, state shapes, compiled) of the packed gossip step of a
    two-layer qwen3 on ``dp`` chips of a described v5e:2x2, one replica
    per chip."""
    cfg = reduced(get_config("qwen3-0.6b"), d_model=512)
    mesh = make_mesh((dp, 1), ("data", "model"), devices=topo.devices[:dp])
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
    if monkeypatch is not None:     # the fused update on its Pallas path
        monkeypatch.setattr(kernel_ops, "interpret", lambda: False)
    compiled = bundle.jitted(0).lower(
        place(state, bundle.state_shardings),
        place(batch, bundle.batch_shardings)).compile()
    return bundle, state, compiled


_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
# ops that hand a whole buffer on unchanged: a view, a move between memory
# spaces, a slice of it or a tuple element
_PASS = {"bitcast", "copy-start", "copy-done", "get-tuple-element",
         "slice-start", "slice-done"}


def _entry(text):
    """{name: (opcode, operand names, line)} of the ENTRY computation, and
    the ROOT's name."""
    body = text[text.index("\nENTRY"):].split("\n", 1)[1]
    body = body[:body.index("\n}")]
    ins, root = {}, None
    for line in body.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, _, op, rest = m.groups()
        depth, args = 1, ""
        for ch in rest:
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
            args += ch
        if op == "custom-call" and 'custom_call_target="ConcatBitcast"' in line:
            op = "bitcast"          # pieces of one buffer laid end to end
        ins[name] = (op, re.findall(r"%([\w.\-]+)", args), line)
        if line.lstrip().startswith("ROOT"):
            root = name
    return ins, root


def _elements(line):
    dims = re.search(r"= \(?\w+\[([\d,]*)\]", line).group(1)
    return math.prod(int(d) for d in dims.split(",") if d)


def _state_relayouts(text):
    """Names of the ENTRY's reduces, copies and copy/reduce fusions that
    read a parameter or momentum bucket of the state, or write one into the
    step's result."""
    ins, root = _entry(text)
    state = {n for n, (op, _, line) in ins.items() if op == "parameter"
             and re.search(r"op_name=\"state\[\\'(params|opt\\'\]\[\\'mom)\\'\]",
                           line)}
    sizes = {_elements(ins[n][2]) for n in state}
    grown = True
    while grown:                    # what the state's buffers pass through
        more = {n for n, (op, args, _) in ins.items()
                if op in _PASS and n not in state and set(args) & state}
        grown = bool(more)
        state |= more
    out, todo = set(), [n for n in ins[root][1]
                        if _elements(ins[n][2]) in sizes]
    while todo:                     # what the result's buffers come from
        n = todo.pop()
        if n in out or n not in ins:
            continue
        out.add(n)
        if ins[n][0] in _PASS:
            todo.extend(ins[n][1])
    relayout = lambda n, op: op in ("reduce", "copy") or (
        op == "fusion" and re.match(r"(copy|\w*reduce)\w*_fusion", n))
    return sorted(n for n, (op, args, _) in ins.items()
                  if relayout(n, op) and (set(args) & state or n in out))


@pytest.mark.parametrize("dp", [1, 4])
def test_packed_step_keeps_buckets_in_kernel_view(topo, dp, monkeypatch):
    """The parameter and momentum buckets are stored in the fused update's
    (rows, 128) view: the compiled step hands them to the kernel and takes
    them back by bitcasts alone, with no reduce or copy of a bucket, and
    the state's arguments hold its bytes with no tile padding."""
    bundle, state, compiled = _packed_gossip_step(topo, dp, monkeypatch)
    text = compiled.as_text()
    assert any(c.startswith("%fused_update") for c in _custom_calls(text))
    assert all(b.shape[-2:] == (n // LANE, LANE) for b, n in zip(
        state["params"].buckets, bundle.layout.bucket_sizes))
    assert _state_relayouts(text) == []
    data = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    batch = 4 * 257 * 4             # the int32 (dp, b, S + 1) tokens
    args = compiled.memory_analysis().argument_size_in_bytes
    # the scalars' and the batch's tiles add a few KiB; a bucket padded to
    # its tiles would add its own size
    assert 0 <= args - (data + batch) // dp < 16 << 10, (args, data)


def test_state_relayouts_counts_the_flat_store(one_chip):
    """The counter above finds the relayouts of a bucket stored flat, as
    ``(1, n)``: XLA:TPU tiles that with the size-1 axis as sublanes, so the
    kernel's (rows, 128) view is a reduce in and a copy out."""
    n = 64 * LANE
    s = _shape(one_chip, (1, n), jnp.bfloat16)

    def step(state, g):
        p, m = fused_sgd_1d(state["params"], g, None, state["opt"],
                            lr=0.1, alpha=0.0, donate=True)
        return {"params": p, "opt": m}

    text = _compile_text(step, [{"params": s, "opt": s}, s], donate=(0,))
    assert len(_state_relayouts(text)) >= 2


def test_step_takes_flash_per_replica_on_four_chips(topo):
    """The gossip step of a two-layer qwen3 at data=4 on a described
    v5e:2x2: every attention site takes the kernels, and no all-gather
    feeds them (each replica's call runs on its own chip)."""
    bundle, _, compiled = _packed_gossip_step(topo, 4)
    text = compiled.as_text()
    assert bundle.attn_paths == {"flash": 1, "dense": 0}
    assert _flash_kernels(text) == {"flash_attention", "flash_attention_dkv",
                                    "flash_attention_dq"}
    assert "all-gather" not in text
    assert "collective-permute" in text
