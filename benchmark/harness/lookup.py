"""Find a cell's files from its name.  There is no table in code: a later
PR adds a cell, a traffic mix, a configuration or a metric by adding files
and entries, and edits nothing that is here.

    cells/<cell>.json                 config, chips, traffic, why
    traffic/<traffic>.json            the parameters one generator reads
    configs/<config>/config.json      sizes as published, and as run
    configs/<config>/model.py         build, batch, flops_per_sample, ...
    configs/<config>/reference.py     plain float32 jax.numpy, no mxnet_tpu
    e2e_metrics/<metric>.py           read(run) -> number
    layer_metrics/<metric>.py         read(run) -> number or None
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(root: str, *parts: str) -> dict:
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def _module(root: str, *parts: str) -> ModuleType:
    path = os.path.join(root, *parts)
    name = "benchmark_" + "_".join(parts)[:-3].replace("/", "_")
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    config_name: str
    chips: int
    traffic_name: str
    config: dict
    traffic: dict
    model: ModuleType
    reference: ModuleType
    end_to_end: list     # BENCHMARK.json entries that apply to this cell
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench_dir: str = BENCH_DIR) -> Cell:
    """Everything `name` needs, loaded from files named after it."""
    spec = _json(bench_dir, "cells", f"{name}.json")
    manifest = _json(os.path.dirname(bench_dir), "BENCHMARK.json")
    if name not in {w["name"] for w in manifest["workloads"]}:
        raise KeyError(f"cell {name!r} has a file but no entry under "
                       "`workloads` in BENCHMARK.json")
    cfg = spec["config"]
    return Cell(
        name=name, config_name=cfg, chips=int(spec["chips"]),
        traffic_name=spec["traffic"],
        config=_json(bench_dir, "configs", cfg, "config.json"),
        traffic=_json(bench_dir, "traffic", f"{spec['traffic']}.json"),
        model=_module(bench_dir, "configs", cfg, "model.py"),
        reference=_module(bench_dir, "configs", cfg, "reference.py"),
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, name)])


def metric_reader(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """The `read` function of e2e_metrics/<name>.py or
    layer_metrics/<name>.py (`kind` is the directory)."""
    return _module(bench_dir, kind, f"{name}.py").read
