"""The trace reduction on a hand-built trace of two devices, and the
per-layer readers on the record it feeds."""
import pytest

from bench import cells, tracing
from bench.tracing import Op, Trace


KERN = "%_unknown_.4 = (bf16[8,128]{1,0}) custom-call(%p, %g, %m)"


def _trace():
    # window [1.0, 2.0]; device 0: two overlapping ops, the update kernel,
    # a collective wait, one op straddling the window's start
    ops = {
        0: [Op("fusion.1", 0.9, 1.1), Op("fusion.2", 1.05, 1.3),
            Op(KERN, 1.5, 1.6),
            Op("collective-permute-done", 1.6, 1.7),
            Op("fusion.1", 1.9, 2.2)],
        1: [Op("fusion.1", 1.0, 1.5), Op(KERN, 1.5, 1.7),
            Op("all-reduce.3", 1.7, 1.8)],
    }
    spans = [("bench.window", 1.0, 2.0), ("bench.input", 1.3, 1.45),
             ("bench.dispatch", 1.7, 1.75), ("bench.drain", 1.8, 1.9)]
    return Trace(ops, spans)


def test_busy_is_the_union_inside_the_window():
    tr = _trace()
    # device 0: [1.0,1.3] + [1.5,1.7] + [1.9,2.0] = 0.6
    assert tracing.busy_s(tr, 0) == pytest.approx(0.6)
    # device 1: [1.0,1.8] = 0.8
    assert tracing.busy_s(tr, 1) == pytest.approx(0.8)
    assert tracing.mean_busy_s(tr) == pytest.approx(0.7)


def test_kernel_and_collective_time():
    tr = _trace()
    kern = lambda op: op.name == KERN
    assert tracing.op_time_s(tr, 0, kern) == pytest.approx(0.1)
    assert tracing.mean_op_time_s(tr, kern) == pytest.approx(0.15)
    assert tracing.mean_op_time_s(tr, tracing.is_collective) == \
        pytest.approx(0.1)
    assert not tracing.is_collective(Op("fusion.7", 0, 1))
    assert tracing.is_collective(Op("collective-permute-start.2", 0, 1))


def test_idle_gaps_named_by_host_span():
    tr = _trace()
    assert tracing.idle_gaps(tr, 0) == [
        (pytest.approx(1.3), pytest.approx(1.5)),
        (pytest.approx(1.7), pytest.approx(1.9))]
    gaps = tracing.top_gaps(tr, device=0)
    assert gaps[0][0].startswith("input@") or gaps[0][0].startswith("drain@")
    assert {g[0].split("@")[0] for g in gaps} == {"input", "drain"}
    assert gaps[0][1] == pytest.approx(0.2)


def test_top_ops_averaged_over_devices():
    top = dict(tracing.top_ops(_trace()))
    # fusion.1: device 0 (0.1 + 0.1) and device 1 (0.5), mean 0.35
    assert top["fusion.1"] == pytest.approx(0.35)
    assert top["%_unknown_.4 bf16[8,128]"] == pytest.approx(0.15)


def test_hlo_text_names():
    """XLA:TPU names ops by their instruction text; containers (while)
    overlap their bodies and stay out of the top ops."""
    body = Op("%fusion.7 = bf16[4,1024]{1,0:T(8,128)} fusion(%p), kind=kLoop",
              1.1, 1.4)
    loop = Op("%while.9 = (s32[], bf16[28,1024]) while((s32[], bf16[28,1024]) "
              "%tuple.1), condition=%c, body=%b", 1.0, 1.5)
    done = Op("%collective-permute-done.3 = bf16[1,8192]{1,0} "
              "collective-permute-done(%collective-permute-start.3)", 1.5, 1.6)
    kern = Op("%_unknown_.25 = (bf16[8,128]{1,0}, bf16[8,128]{1,0}) "
              "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"",
              1.6, 1.7)
    tr = Trace({0: [loop, body, done, kern]}, [("bench.window", 1.0, 2.0)])
    assert body.short == "%fusion.7 bf16[4,1024]" and loop.container
    assert not body.container and tracing.is_collective(done)
    assert not tracing.is_collective(body)
    assert [n for n, _ in tracing.top_ops(tr)] == [
        "%fusion.7 bf16[4,1024]", "%collective-permute-done.3 bf16[1,8192]",
        "%_unknown_.25 bf16[8,128]"]
    assert tracing.busy_s(tr, 0) == pytest.approx(0.7)
    reader = cells.metric_reader("update_roofline")
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ur", cells.BENCH / "metrics" / "update_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.KERNEL.match(kern.name) and not mod.KERNEL.match(body.name)
    assert not mod.KERNEL.match(done.name)
    assert reader is not None


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        Trace({0: []}, []).window()


def _record(trace):
    return {"cell": "x", "chips": 2, "steps": 2, "window_s": 1.0,
            "tokens_per_s_per_chip": 1000.0, "setup_build_s": 3.0,
            "setup_warm_s": 4.0, "input_spans_s": [0.001, 0.003],
            "flops_per_token": 1.97e9,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "update": {"bucket_elems": [819e9 * 0.01 / 5], "param_bytes": 1,
                       "grad_bytes": 1, "moment_bytes": [1],
                       "partner": False},
            "trace": trace}


def test_metric_readers_on_a_record():
    rec = _record(_trace())
    read = lambda name: cells.metric_reader(name)(rec)
    assert read("setup.build_s") == 3.0 and read("setup.warm_s") == 4.0
    assert read("input_host_ms") == pytest.approx(2.0)
    assert read("step_mfu") == pytest.approx(1.0)
    assert read("device_idle_pct") == pytest.approx(30.0)
    assert read("exchange_wait_ms") == pytest.approx(50.0)
    # bytes 819e9 * 0.01 over 819 GB/s: 10 ms per step, 20 ms for two
    # steps, against 150 ms of kernel time
    assert read("update_roofline") == pytest.approx(100 * 0.02 / 0.15)


def test_readers_return_nothing_without_a_trace():
    rec = _record(None)
    for name in ("update_roofline", "exchange_wait_ms", "device_idle_pct"):
        assert cells.metric_reader(name)(rec) is None
    one_device = Trace({0: [Op("fusion.1", 1.0, 2.0)]},
                       [("bench.window", 1.0, 2.0)])
    assert cells.metric_reader("exchange_wait_ms")(_record(one_device)) is None
    assert cells.metric_reader("update_roofline")(_record(one_device)) is None
