"""End-to-end dry-run integration: run_one() lowers+compiles a cheap
(arch, shape, mesh) combo against 512 forced host devices in a subprocess and
returns a complete roofline record. This is the same path the 80-combo sweep
exercises (results in experiments/dryrun)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import json
from repro.launch.dryrun import run_one
rec = run_one("falcon-mamba-7b", "long_500k", multi_pod=False,
              protocol="gossip", verbose=False)
assert rec["chips"] == 256 and rec["mesh"] == "16x16"
assert rec["kind"] == "decode"
assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")
assert rec["collectives"]["wire_bytes"] >= 0
assert rec["memory_analysis"]["temp_size_in_bytes"] > 0
assert rec["params"] > 7e9  # falcon-mamba ~7.3B
print("REC_OK", json.dumps(rec["roofline"]))

# pure_dp paper-layout protocol comparison invariant: gossip emits
# collective-permutes and zero all-reduce for the DP exchange
rec_g = run_one("qwen3-0.6b", "train_4k", multi_pod=False,
                protocol="gossip", dist_mode="pure_dp", verbose=False)
rec_a = run_one("qwen3-0.6b", "train_4k", multi_pod=False,
                protocol="agd", dist_mode="pure_dp", verbose=False)
cg, ca = rec_g["collectives"], rec_a["collectives"]
assert cg["collective-permute_count"] > 0
assert cg["all-reduce_bytes"] < 0.05 * ca["all-reduce_bytes"]
assert cg["wire_bytes"] < 0.75 * ca["wire_bytes"]  # paper: ~0.5x
print("PROTO_OK")
"""


@pytest.mark.slow
def test_dryrun_run_one_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # CPU experiment: never reach for a chip
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "REC_OK" in r.stdout and "PROTO_OK" in r.stdout
