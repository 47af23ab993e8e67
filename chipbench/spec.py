"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
Each lives in a file of its own, found by name:

    chipbench/configs/<file named by the configuration entry>.json
    chipbench/traffic/<traffic>.json
    chipbench/limits/<workload>.json        the correctness limits
    chipbench/metrics/<metric>.py           one reader per metric
    chipbench/references/<reference>.py     named by the configuration file

Adding a cell, a mix or a metric adds files and entries; no code changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_module(path: Path):
    """Import a file by path: names may hold dots and dashes."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_").replace("-", "_"),
        path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _for_cell(metrics: list[dict], name: str) -> list[dict]:
    return [m for m in metrics if "workloads" not in m or name in m["workloads"]]


def resolve(name: str, bench: dict | None = None, root: Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    bench_dir = root / "chipbench"
    return Cell(
        name=name,
        chips=w["chips"],
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((bench_dir / "limits" / f"{name}.json").read_text()),
        end_to_end=_for_cell(bench["end_to_end"], name),
        per_layer=_for_cell(bench["per_layer"], name),
    )


def reader(metric: str, root: Path = ROOT):
    """The metric's ``read(run) -> float | None``."""
    return load_module(root / "chipbench" / "metrics" / f"{metric}.py").read


def reference(config: dict, root: Path = ROOT):
    return load_module(root / "chipbench" / "references" / f"{config['reference']}.py")
