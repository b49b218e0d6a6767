"""End-to-end integration on a single device: Trainer loop convergence per
protocol, loss wiring (MoE aux, MTP), serving engine generation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import ShardedTokenDataset
from repro.launch.mesh import make_smoke_mesh
from repro.launch.specs import train_input_specs
from repro.models import lm_init, reduced
from repro.optim import sgd
from repro.serve import ServingEngine
from repro.train import (Trainer, init_train_state, make_distribution,
                         make_train_step_bundle)


def _tiny_cfg(arch="qwen3-0.6b", d_model=64):
    return dataclasses.replace(reduced(get_config(arch), d_model=d_model),
                               param_dtype="float32",
                               compute_dtype="float32")


def _bundle(cfg, protocol, seq_len=24, global_batch=4, lr=0.3):
    mesh = make_smoke_mesh(1, 1)
    dist = make_distribution(mesh, "replica")
    opt = sgd(lr, momentum=0.9)
    state_shapes, state_axes, batch_shapes = train_input_specs(
        cfg, dist, seq_len, global_batch, opt)
    bundle = make_train_step_bundle(
        cfg, dist, opt, state_shapes=state_shapes, state_axes=state_axes,
        batch_shapes=batch_shapes, protocol=protocol, remat=False)
    state, _ = init_train_state(jax.random.key(0), cfg, dist, opt)
    return bundle, state, dist


@pytest.mark.parametrize("protocol", ["gossip", "agd"])
def test_trainer_loss_decreases(protocol):
    cfg = _tiny_cfg()
    bundle, state, dist = _bundle(cfg, protocol)
    ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=24, n_shards=1,
                             batch_per_shard=4, seed=0)
    tr = Trainer(bundle, state, ds, log_every=0)
    hist = tr.run(30)
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert last < first - 0.2, (first, last)


def test_moe_arch_trains_with_aux():
    cfg = _tiny_cfg("kimi-k2-1t-a32b")
    bundle, state, dist = _bundle(cfg, "gossip", lr=0.1)
    ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=24, n_shards=1,
                             batch_per_shard=4)
    tr = Trainer(bundle, state, ds, log_every=0)
    hist = tr.run(6)
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert hist[0]["moe_aux"] > 0.0


def test_mtp_arch_loss_includes_term():
    cfg = _tiny_cfg("deepseek-v3-671b")
    assert cfg.mtp
    bundle, state, dist = _bundle(cfg, "agd", lr=0.05)
    ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=24, n_shards=1,
                             batch_per_shard=2)
    tr = Trainer(bundle, state, ds, log_every=0)
    hist = tr.run(3)
    assert "mtp_ce" in hist[0]
    assert hist[0]["loss"] > hist[0]["ce"]  # aux terms contribute


def test_serving_engine_generates():
    cfg = _tiny_cfg()
    params, _ = lm_init(jax.random.key(0), cfg)
    eng = ServingEngine(cfg, params, max_seq=64)
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (3, 8)).astype(np.int32)
    out = eng.generate(prompts, max_new_tokens=5)
    assert out.shape == (3, 5)
    assert (out >= 0).all() and (out < cfg.vocab).all()
    # greedy decoding is deterministic
    out2 = eng.generate(prompts, max_new_tokens=5)
    np.testing.assert_array_equal(out, out2)


def test_serving_engine_vlm_stub():
    cfg = _tiny_cfg("llava-next-mistral-7b")
    params, _ = lm_init(jax.random.key(0), cfg)
    eng = ServingEngine(cfg, params, max_seq=64)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (2, 6)).astype(np.int32)
    img = rng.normal(size=(2, cfg.vision.n_image_tokens, cfg.d_model)).astype(np.float32) * 0.02
    out = eng.generate(prompts, max_new_tokens=3, image_embeds=img)
    assert out.shape == (2, 3)


def test_launcher_summary_reports_attn_paths():
    """The step counts its attention sites by path while tracing: the
    smoke qwen3's scanned layers are one causal site the flash kernels
    tile. The launcher's summary reports them as lowered here, off a TPU:
    every site computes ``_sdpa``."""
    from repro.launch import train as launcher
    argv = ["--smoke", "--steps", "1", "--log-every", "0", "--seq-len",
            "128", "--global-batch", "2"]
    trainer, _ = launcher.build_trainer(launcher.build_parser().parse_args(
        argv))
    trainer.run(1)
    assert trainer.bundle.attn_paths == {"flash": 1, "dense": 0}
    assert launcher.run(argv)["attn_paths"] == {"flash": 0, "dense": 1}
