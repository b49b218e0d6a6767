"""BENCHMARK.json: every cell resolves to its files by name, names and
units keep to the allowed characters, and a new configuration, mix, metric
and cell are found by name once their files exist."""
import json
import re
import shutil

import pytest

from bench import cells

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def test_top_level_keys_and_command():
    assert set(BENCH) == TOP_KEYS
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1].startswith("bench/")
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_its_files(cell):
    c = cells.resolve(BENCH, cell)
    assert c.config["arch"] and c.traffic["seq_len"] > 0
    assert set(c.limits) >= {"loss_gap", "grad_gap", "delta_gap"}
    for m in c.per_layer:
        assert callable(cells.metric_reader(m["name"]))
    names = {m["name"] for m in c.end_to_end}
    assert {"setup_s", "tokens_per_s_per_chip", "peak_hbm_gb"} <= names


def test_names_units_and_keys():
    seen = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        for k in ("name", "config", "traffic"):
            assert NAME.match(w[k]), w[k]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert {"layer", "moves"} <= set(m) and "bound" not in m
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_new_files_are_found_by_name(tmp_path):
    """A later change adds a model, a mix, a metric and a cell as files and
    entries only; the harness finds each by its name."""
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (bench_dir / sub).mkdir(parents=True)
    shutil.copy(cells.BENCH / "configs" / "olmo-1b.json",
                bench_dir / "configs" / "new-model.json")
    shutil.copy(cells.BENCH / "traffic" / "gossip-s2048-b2.json",
                bench_dir / "traffic" / "new-mix.json")
    (bench_dir / "limits" / "new-model.new-mix.1chip.json").write_text(
        json.dumps({"loss_gap": 1, "grad_gap": 1, "delta_gap": 1}))
    (bench_dir / "metrics" / "new.metric_ms.py").write_text(
        "def read(rec):\n    return rec['x'] * 2\n")
    spec = dict(BENCH)
    spec["configs"] = [{"name": "new-model", "source": "x",
                        "file": "bench/configs/new-model.json",
                        "reduced": [], "why": "x"}]
    spec["workloads"] = [{"name": "new-model.new-mix.1chip",
                          "config": "new-model", "traffic": "new-mix",
                          "chips": 1, "why": "x"}]
    spec["per_layer"] = [{"name": "new.metric_ms", "unit": "ms",
                          "better": "lower", "source": "program_span",
                          "layer": "Input", "moves": "setup_s"}]
    c = cells.resolve(spec, "new-model.new-mix.1chip", bench_dir=bench_dir)
    assert c.config["arch"] == "olmo-1b" and c.traffic["seq_len"] == 2048
    assert [m["name"] for m in c.per_layer] == ["new.metric_ms"]
    assert cells.metric_reader("new.metric_ms", bench_dir)({"x": 3}) == 6
    with pytest.raises(KeyError):
        cells.resolve(spec, "no-such-cell", bench_dir=bench_dir)


def test_launcher_argv_from_files():
    c = cells.resolve(BENCH, "qwen3-0.6b.1k.1chip")
    c.chips = 4
    argv = c.launcher_argv()
    assert argv[:2] == ["--arch", "qwen3-0.6b"]
    assert "--packed" in argv
    assert argv[argv.index("--global-batch") + 1] == "16"
    assert argv[argv.index("--seq-len") + 1] == "1024"
    assert argv[argv.index("--log-every") + 1] == "0"


def test_metric_scoped_by_its_workloads_key():
    scoped = {"name": "exchange_wait_ms", "workloads": ["x.4chip"]}
    assert cells.metric_applies(scoped, "x.4chip")
    assert not cells.metric_applies(scoped, "qwen3-0.6b.1k.1chip")
    assert cells.metric_applies({"name": "step_mfu"}, "qwen3-0.6b.1k.1chip")
