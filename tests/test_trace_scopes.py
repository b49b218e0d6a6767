"""What a profiler trace of the program can be split by: the step program's
named scopes (``fwd``, ``sdpa``, ``update``, ``exchange``, ``shuffle``; JAX
adds ``transpose(...)`` for the backward and ``rematted_computation`` for the
remat recompute) and the trainer's host spans (``repro.*``)."""
import dataclasses
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import ShardedTokenDataset
from repro.launch.mesh import make_smoke_mesh
from repro.launch.specs import train_input_specs
from repro.models import reduced
from repro.optim import sgd
from repro.train import (Trainer, init_train_state, make_distribution,
                         make_train_step_bundle)

# the tiny packed gossip step on four replicas, remat on: the op_name of
# every dot (before optimisation) and of every compiled instruction
CHILD = r"""
import dataclasses, json, re
import jax
from repro.configs import get_config
from repro.launch.mesh import make_smoke_mesh
from repro.launch.specs import train_input_specs
from repro.models import reduced
from repro.optim import sgd
from repro.train import (init_train_state, make_distribution,
                         make_train_step_bundle)

cfg = dataclasses.replace(reduced(get_config("qwen3-0.6b"), d_model=64),
                          param_dtype="float32", compute_dtype="float32")
dist = make_distribution(make_smoke_mesh(4, 1), cfg.dist_mode)
opt = sgd(0.1, momentum=0.9)
shapes, axes, batch = train_input_specs(cfg, dist, 32, 8, opt)
bundle = make_train_step_bundle(
    cfg, dist, opt, state_shapes=shapes, state_axes=axes, batch_shapes=batch,
    protocol="gossip", gossip_packed=True, remat=True)
state = jax.eval_shape(lambda k: init_train_state(
    k, cfg, dist, opt, packed=True, layout=bundle.layout)[0],
    jax.random.key(0))
lowered = bundle.jitted(0).lower(state, batch)
INSTR = re.compile(r'^\s*(?:ROOT )?%?\S+ = .*? ([a-z][\w-]*)\((.*)$')
CALLS = re.compile(r'(?:to_apply|body|condition|calls)=%?([\w.-]+)')

def ops(text):
    # (opcode, op_name) of every instruction; an op_name inside a called
    # computation, which is relative to its caller's, is resolved against it
    comps, cur = {}, None
    for line in text.splitlines():
        if line.endswith("{") and " = " not in line:
            head = line.split()
            cur = comps.setdefault(
                head[head[0] == "ENTRY"].lstrip("%"), [])
            if line.startswith("ENTRY"):
                entry = cur
        elif cur is not None and (m := INSTR.match(line)):
            name = re.search(r'op_name="([^"]*)"', m.group(2))
            cur.append((m.group(1), name.group(1) if name else "",
                        CALLS.findall(m.group(2))))
    out = []

    def walk(instrs, prefix):
        for op, name, called in instrs:
            if prefix and name and not name.startswith("jit("):
                name = prefix + "/" + name
            out.append((op, name))
            for c in called:
                walk(comps[c], name or prefix)

    walk(entry, "")
    return out

from jax._src.lib import xla_client
opts = xla_client._xla.HloPrintOptions.short_parsable()
opts.print_metadata = True
module = lowered.compiler_ir("hlo").as_hlo_module()
print(json.dumps({"lowered": ops(module.to_string(opts)),
                  "compiled": ops(lowered.compile().as_text())}))
"""


def _segment(name):
    """``name`` as a whole path segment (``update``, not
    ``dynamic_update_slice``) or a transform's argument (``jvp(fwd)``)."""
    return re.compile(r"(^|[/(])" + name + r"([/)]|$)")


RULES = ((_segment("exchange"), "exchange"), (_segment("shuffle"), "shuffle"),
         (_segment("update"), "update"),
         (_segment("rematted_computation"), "recompute"),
         (re.compile(r"(^|[/(])transpose\("), "bwd"), (_segment("fwd"), "fwd"))


def _phase(op_name: str) -> str:
    return next((phase for rule, phase in RULES if rule.search(op_name)),
                "unscoped")


def test_step_program_is_scoped_by_phase():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    dots = [name for op, name in out["lowered"] if op == "dot"]
    # every matmul of the model is in the loss: its forward, its backward
    # or the recompute of a checkpointed layer
    assert dots and {_phase(n) for n in dots} == {"fwd", "bwd", "recompute"}
    assert any("sdpa" in n for n in dots)
    compiled = out["compiled"]
    permutes = [n for op, n in compiled if op.startswith("collective-permute")]
    # the gossip ppermute sits inside the fused update, the batch ring
    # shuffle in its own scope
    assert {_phase(n) for n in permutes} == {"exchange", "shuffle"}
    assert any("update/" in n and "exchange/" in n for n in permutes)
    phases = {_phase(n) for _, n in compiled}
    assert {"fwd", "bwd", "recompute", "update", "exchange"} <= phases


def _tiny_trainer():
    cfg = dataclasses.replace(reduced(get_config("qwen3-0.6b"), d_model=32),
                              param_dtype="float32", compute_dtype="float32")
    dist = make_distribution(make_smoke_mesh(1, 1), "replica")
    opt = sgd(0.1, momentum=0.9)
    shapes, axes, batch = train_input_specs(cfg, dist, 16, 2, opt)
    bundle = make_train_step_bundle(
        cfg, dist, opt, state_shapes=shapes, state_axes=axes,
        batch_shapes=batch, protocol="gossip", remat=False)
    state, _ = init_train_state(jax.random.key(0), cfg, dist, opt)
    ds = ShardedTokenDataset(vocab=cfg.vocab, seq_len=16, n_shards=1,
                             batch_per_shard=2, seed=0)
    return Trainer(bundle, state, ds, log_every=0)


def test_trainer_writes_its_host_spans(tmp_path):
    from jax.profiler import ProfileData

    tr = _tiny_trainer()
    tr.run(2)
    jax.profiler.start_trace(str(tmp_path))
    tr.run(3, start_step=2)
    jax.block_until_ready(tr.state)
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro.")]
    names = {s[0] for s in spans}
    assert {"repro.step", "repro.input", "repro.dispatch",
            "repro.drain"} <= names
    steps = [s for s in spans if s[0] == "repro.step"]
    assert [s[3]["step_num"] for s in steps] == [2, 3, 4]
    assert all(s[3]["phase"] == 0 for s in steps)
    # each dispatch happens inside its step's span
    for name, t0, t1, _ in spans:
        if name == "repro.dispatch":
            assert any(s0 <= t0 and t1 <= s1 for _, s0, s1, _ in steps)


def test_drain_keeps_every_value():
    """One ``device_get`` for the whole window gives the history that one
    ``float()`` per scalar gave."""
    tr = _tiny_trainer()
    vals = np.float32([1 / 3, 2.5e-7, 11.75, np.nan])
    pending = [(7, {"loss": jnp.asarray(vals[0]), "ce": jnp.asarray(vals[1])}),
               (8, {"loss": jnp.asarray(vals[2]), "ce": jnp.asarray(vals[3])})]
    want = [dict({k: float(v) for k, v in m.items()}, step=s)
            for s, m in pending]
    tr._inflight.append(pending[-1][1]["loss"])
    tr._drain(pending)
    assert pending == [] and not tr._inflight
    got = tr.history
    assert [h["step"] for h in got] == [7, 8]
    for g, w in zip(got, want):
        assert set(g) == set(w)
        for k in w:
            assert type(g[k]) is type(w[k])
            assert g[k] == w[k] or (np.isnan(g[k]) and np.isnan(w[k]))

