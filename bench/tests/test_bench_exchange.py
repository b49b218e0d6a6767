"""Four replicas on forced-host CPU devices: the gossip run is correct
against the reference's exchange, and with the exchange left out of the
timed path it is not."""
import json
import os
import subprocess
import sys

from bench import cells

CHILD = r"""
import functools, json, sys, time
from unittest import mock
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
from bench import harness
from bench.tests.tiny import tiny_cell
import repro.launch.train as launcher
out = {}
for fault in ("none", "no_exchange"):
    with mock.patch.object(launcher, "make_train_step_bundle", functools.partial(
            launcher.make_train_step_bundle,
            **({"gossip_alpha": 0.0} if fault == "no_exchange" else {}))):
        res = harness.run_cell(tiny_cell(dp=4), 2_147_483_659, 0.2, False,
                               t0=time.perf_counter())
    out[fault] = {"correct": res["correct"], "checks": res["checks"],
                  "count": res["device"]["count"]}
print(json.dumps(out))
"""


def test_exchange_left_out_is_caught():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", CHILD, str(cells.ROOT)],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["none"]["count"] == 4
    assert out["none"]["correct"], out["none"]["checks"]
    assert not out["no_exchange"]["correct"]
    assert out["no_exchange"]["checks"]["delta_gap"]["value"] > 0.05
