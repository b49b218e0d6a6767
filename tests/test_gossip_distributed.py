"""shard_map/ppermute gossip == the replica simulator, on 8 forced host
devices (subprocess so the device-count override never leaks into this
process — smoke tests must see 1 CPU device)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import (build_schedule, make_gossip_mix, gossip_mix_sim,
                        make_ring_shuffle)

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
p = 4
sched = build_schedule(p, num_rotations=2, seed=3)
rng = np.random.default_rng(0)
# params: leading replica axis 4 over "data", second dim sharded over "model"
w = jnp.asarray(rng.normal(size=(p, 8, 6)), jnp.float32)
specs = {"w": P("data", "model", None)}
params = {"w": jax.device_put(w, NamedSharding(mesh, P("data", "model", None)))}

for mode in ("static", "dynamic"):
    mix = make_gossip_mix(mesh, ("data",), sched, specs, mode=mode)
    got = {"w": w}
    got = jax.device_put(got, {"w": NamedSharding(mesh, specs["w"])})
    want = {"w": w}
    for t in range(sched.period + 2):
        got = mix(got, t if mode == "static" else jnp.int32(t))
        want = gossip_mix_sim(want, jnp.asarray(sched.recv_from(t)))
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(want["w"]),
                               rtol=1e-5, atol=1e-6)
    print(f"ok mode={mode}")

# ring shuffle: shard i moves to rank (i+1) % p
batch = jnp.arange(p * 3 * 2, dtype=jnp.float32).reshape(p, 3, 2)
bspecs = P("data", None, None)
sh = make_ring_shuffle(mesh, ("data",), bspecs)
rotated = sh(jax.device_put(batch, NamedSharding(mesh, bspecs)))
np.testing.assert_allclose(np.asarray(rotated), np.roll(np.asarray(batch), 1, axis=0))
print("ok ring shuffle")

# alpha != 0.5 generalized mix
mix = make_gossip_mix(mesh, ("data",), sched, specs, alpha=0.25)
got = mix({"w": jax.device_put(w, NamedSharding(mesh, specs["w"]))}, 0)
recv = np.asarray(w)[np.asarray(sched.recv_from(0))]
np.testing.assert_allclose(np.asarray(got["w"]), 0.75*np.asarray(w) + 0.25*recv, rtol=1e-6)
print("ok alpha mix")
print("ALL_OK")
"""


@pytest.mark.slow
def test_shardmap_gossip_matches_simulator():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL_OK" in r.stdout


_KERNEL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import build_schedule, make_gossip_mix, gossip_mix_sim
from repro.kernels import gossip_mix_tree

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
p = 4
sched = build_schedule(p, num_rotations=2, seed=5)
rng = np.random.default_rng(1)
w = jnp.asarray(rng.normal(size=(p, 8, 6)), jnp.float32)
specs = {"w": P("data", "model", None)}

# gossip mix with the Pallas gossip_mix kernel as mix_impl
mix = make_gossip_mix(mesh, ("data",), sched, specs,
                      mix_impl=lambda a, b, alpha: gossip_mix_tree(a, b, alpha))
got = {"w": jax.device_put(w, NamedSharding(mesh, specs["w"]))}
want = {"w": w}
for t in range(3):
    got = mix(got, t)
    want = gossip_mix_sim(want, jnp.asarray(sched.recv_from(t)))
np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(want["w"]),
                           rtol=1e-5, atol=1e-6)
print("KERNEL_MIX_OK")
"""


@pytest.mark.slow
def test_gossip_with_pallas_mix_kernel():
    """The Pallas gossip_mix kernel plugs into the distributed protocol as
    mix_impl and matches the simulator."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", _KERNEL_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "KERNEL_MIX_OK" in r.stdout
