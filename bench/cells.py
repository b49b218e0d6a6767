"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``), a traffic
mix (``bench/traffic/<traffic>.json``) and its chips; its limits are in
``bench/limits/<cell>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``. Adding a model, a mix, a metric or a cell is
adding files and entries: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import List

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# a large step count keeps the launcher's step-decay schedule at its base
# learning rate for every step a run takes
LAUNCH_STEPS = 1_000_000


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    def launcher_argv(self) -> List[str]:
        t = self.traffic
        argv = ["--arch", self.config["arch"]]
        for flag, value in t["launcher"].items():
            if value is True:
                argv.append(flag)
            elif value is not False:
                argv += [flag, str(value)]
        argv += ["--seq-len", str(t["seq_len"]),
                 "--global-batch", str(t["seqs_per_chip"] * self.chips),
                 "--steps", str(LAUNCH_STEPS), "--log-every", "0"]
        return argv + [str(a) for a in self.config.get("launcher", [])]


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def metric_applies(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def resolve(bench: dict, workload: str,
            bench_dir: pathlib.Path = BENCH) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=workload, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=load_json(bench_dir.parent / cfg_entry["file"]),
        traffic=load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(bench_dir / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"]
                    if metric_applies(m, workload)],
        per_layer=[m for m in bench["per_layer"]
                   if metric_applies(m, workload)])


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH):
    """The ``read(record) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
