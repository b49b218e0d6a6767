"""Logical-axis -> PartitionSpec rules (no multi-device needed: meshes over
1 device still validate spec construction logic via abstract axis sizes is
not possible, so we build tiny meshes and check rule outcomes)."""
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_smoke_mesh
from repro.train.sharding import Distribution


def _mesh1():
    # single real device: mesh (1,1) exercises rule selection; axis sizes of
    # 1 make every divisibility test pass trivially, so for divisibility we
    # fake sizes via a spec-level unit test below.
    return make_smoke_mesh(1, 1)


def test_replica_mode_rules():
    d = Distribution(_mesh1(), "replica")
    assert d.dp_axes == ("data",)
    # heads -> model; embed -> replicated
    s = d.leaf_spec((4, 8, 16), "embed,heads,head_dim", False)
    assert s == P(None, "model", None)
    # vocab -> model
    assert d.leaf_spec((32, 4), "vocab,embed", False) == P("model", None)


def test_fsdp_mode_rules():
    d = Distribution(_mesh1(), "fsdp")
    assert d.dp_axes == ()
    s = d.leaf_spec((4, 8, 16), "embed,heads,head_dim", False)
    assert s == P("data", "model", None)
    # experts + embed both shardable, expert_ffn replicated
    s = d.leaf_spec((4, 8, 16), "experts,embed,expert_ffn", False)
    assert s == P("model", "data", None)


def test_no_mesh_axis_used_twice():
    d = Distribution(_mesh1(), "replica")
    # heads and kv_heads both want "model": second one must fall back
    s = d.leaf_spec((4, 4, 2), "heads,kv_heads,", False)
    assert s == P("model", None, None)


def test_replica_axis_prefix():
    d = Distribution(_mesh1(), "replica")
    s = d.leaf_spec((8, 16), "embed,ffn", True)
    assert s == P("data", None, "model")


def test_batch_rule_takes_data_axes():
    d = Distribution(_mesh1(), "replica")
    assert d.leaf_spec((4,), "batch", False) == P("data")
    s = d.leaf_spec((4, 2, 8, 16), "batch,kv_seq,kv_heads,", False)
    # batch takes the data axis; kv_seq can't reuse "data"; kv_heads divides
    # the (size-1) model axis here, so it shards
    assert s == P("data", None, "model", None)


class _FakeMesh:
    """Duck-typed mesh with arbitrary axis sizes (spec logic only)."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def _dist(shape, mode):
    d = Distribution.__new__(Distribution)
    mesh = _FakeMesh(shape)
    d.mesh = mesh
    d.mode = mode
    d.axis_names = tuple(mesh.axis_names)
    d.multi_pod = "pod" in d.axis_names
    d.batch_axes = tuple(a for a in ("pod", "data") if a in d.axis_names)
    d.dp_axes = d.batch_axes if mode == "replica" else (
        ("pod",) if d.multi_pod else ())
    d.dp = int(np.prod([mesh.shape[a] for a in d.dp_axes])) if d.dp_axes else 1
    return d


def test_divisibility_fallback_production_sizes():
    d = _dist({"data": 16, "model": 16}, "replica")
    # 8 kv heads cannot shard over 16-way model axis -> replicated
    assert d.leaf_spec((64, 8, 128), "embed,kv_heads,head_dim", False) == \
        P(None, None, None)
    # 48 heads CAN (48 % 16 == 0)
    assert d.leaf_spec((64, 48, 128), "embed,heads,head_dim", False) == \
        P(None, "model", None)
    # batch=1 cannot shard -> kv_seq takes data
    assert d.leaf_spec((1, 524288, 8, 128), "batch,kv_seq,kv_heads,", False) \
        == P(None, "data", None, None)
    # batch=128 takes data; kv_seq falls back
    assert d.leaf_spec((128, 32768, 8, 128), "batch,kv_seq,kv_heads,", False) \
        == P("data", None, None, None)


def test_multipod_specs():
    d = _dist({"pod": 2, "data": 16, "model": 16}, "replica")
    assert d.dp == 32
    s = d.leaf_spec((8, 16), "embed,ffn", True)
    assert s == P(("pod", "data"), None, "model")
    d2 = _dist({"pod": 2, "data": 16, "model": 16}, "fsdp")
    assert d2.dp == 2
    assert d2.dp_axes == ("pod",)
    s2 = d2.leaf_spec((32, 16), "embed,ffn", True)
    assert s2 == P("pod", "data", "model")
    # batch rule uses pod+data jointly: 256 % 32 == 0
    assert d2.leaf_spec((256, 10), "batch,", False) == P(("pod", "data"), None)
