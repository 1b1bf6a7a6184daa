"""The benchmark's own tests: `python -m pytest benchmark/tests -q` on the
CPU.  Not part of tier-1; nothing here measures anything."""
from __future__ import annotations

import hashlib
import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path[:0] = [REPO, BENCH]

from harness import lookup, peaks, result, window  # noqa: E402

MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# ---- BENCHMARK.json against the contract's limits ----------------------

def test_manifest_is_within_the_contract():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert isinstance(MANIFEST["run_seconds"], int)
    # 2 + 14 x cells runs of run_seconds + 60, 2 x 90 more a cell, 1200
    # spare, inside 43200 s, at the full 24 cells
    assert (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in MANIFEST[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in MANIFEST[k]}) == len(MANIFEST[k])
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in MANIFEST["end_to_end"]:
        assert set(m) == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert c["file"].startswith(MANIFEST["paths"][0] + "/")
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    for root, _dirs, files in os.walk(BENCH):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.fullmatch(r"[A-Za-z0-9_.-]+", f), (root, f)


# ---- every name resolves to its files ----------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == name)
    cell = lookup.cell(name)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        entry["config"], entry["traffic"], entry["chips"])
    spec = json.load(open(os.path.join(BENCH, "cells", f"{name}.json")))
    assert spec["why"] == entry["why"]
    config = next(c for c in MANIFEST["configs"]
                  if c["name"] == entry["config"])
    assert config["file"] == f"benchmark/configs/{entry['config']}/config.json"
    assert cell.config["source"] == config["source"]
    assert cell.config["reduced"] == config["reduced"]
    for fn in ("build", "batch", "sample", "system_logits",
               "reference_logits", "reference_first_loss",
               "flops_per_sample"):
        assert callable(getattr(cell.model, fn))
    assert cell.model.SAMPLES_UNIT == cell.config["samples_unit"]
    assert {m["name"] for m in cell.end_to_end} >= {"throughput", "setup_s"}
    assert cell.per_layer
    for kind, ms in (("e2e_metrics", cell.end_to_end),
                     ("layer_metrics", cell.per_layer)):
        for m in ms:
            assert callable(lookup.metric_reader(kind, m["name"]))
    has_collectives = any(m["name"] == "collective_ms"
                          for m in cell.per_layer)
    assert has_collectives == (cell.chips == 4)


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_reference_never_imports_the_program(config):
    src = open(os.path.join(BENCH, "configs", config, "reference.py")).read()
    assert not re.search(r"^\s*(import|from)\s+mxnet_tpu", src, re.M)
    assert 'default_matmul_precision("highest")' in src


def _digests(root):
    out = {}
    for d, _dirs, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha1(
                open(p, "rb").read()).hexdigest()
    return out


def test_additions_need_no_edit(tmp_path):
    """A cell, a traffic mix, a configuration and a per-layer metric
    dropped into a copy are found by name; no file that was there
    changes except BENCHMARK.json, which only gains entries."""
    ignore = shutil.ignore_patterns("__pycache__", "data")
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=ignore)
    bench = str(tmp_path / "benchmark")
    before = _digests(bench)

    shutil.copytree(os.path.join(bench, "configs", "bert_base"),
                    os.path.join(bench, "configs", "bert_wide"))
    with open(os.path.join(bench, "traffic", "s64_pred10.json"), "w") as f:
        json.dump({"batch": 8, "seq_len": 64, "max_predictions": 10,
                   "masked_lm_prob": 0.15, "short_seq_prob": 0.1}, f)
    with open(os.path.join(bench, "cells", "bert_wide_s64.json"), "w") as f:
        json.dump({"config": "bert_wide", "chips": 1,
                   "traffic": "s64_pred10", "why": "a test"}, f)
    with open(os.path.join(bench, "layer_metrics", "steps_traced.py"),
              "w") as f:
        f.write("def read(run):\n    return run['trace'].steps\n")
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["workloads"].append({
        "name": "bert_wide_s64", "config": "bert_wide",
        "traffic": "s64_pred10", "chips": 1, "why": "a test"})
    manifest["per_layer"].append({
        "name": "steps_traced", "unit": "steps", "better": "higher",
        "source": "device_trace", "layer": "device", "moves": "throughput",
        "workloads": ["bert_wide_s64"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)

    cell = lookup.cell("bert_wide_s64", bench_dir=bench)
    assert cell.traffic["seq_len"] == 64
    assert cell.model.__file__.startswith(bench)
    assert "steps_traced" in {m["name"] for m in cell.per_layer}
    assert "collective_ms" not in {m["name"] for m in cell.per_layer}
    read = lookup.metric_reader("layer_metrics", "steps_traced", bench)

    class T:
        steps = 8
    assert read({"trace": T}) == 8
    # the old cells do not see the new metric
    assert "steps_traced" not in {
        m["name"] for m in lookup.cell("bert_base_s128", bench).per_layer}
    after = _digests(bench)
    assert {k: after[k] for k in before} == before


# ---- FLOPs per sample, pinned -------------------------------------------

def test_resnet50_v1_flops_from_the_layer_shapes():
    cell = lookup.cell("resnet50_bs256")
    macs = cell.model.forward_macs(cell.config, 224)
    # He et al. Table 1 gives 3.8e9 for the 50-layer net; counted layer
    # by layer with v1's stride on the first 1x1 it is 3.858e9
    assert abs(macs - 3.858e9) / 3.858e9 < 0.01
    assert cell.model.flops_per_sample(cell.config, cell.traffic) == 6 * macs
    # v1.5 (stride on the 3x3) would be 4.09e9: not what the zoo builds
    assert abs(macs - 4.09e9) / 4.09e9 > 0.04


@pytest.mark.parametrize("name", ["bert_base_s512", "bert_base_s128"])
def test_bert_base_flops_against_six_times_parameters(name):
    cell = lookup.cell(name)
    c, t = cell.config, cell.traffic
    s, p, h = t["seq_len"], t["max_predictions"], c["units"]
    encoder_params = c["num_layers"] * (4 * h * h + 2 * h * c["hidden_size"])
    attention = 3 * 2 * c["num_layers"] * 2 * s * s * h
    head = 3 * 2 * p * (h * h + h * c["vocab_size"])
    estimate = 6 * encoder_params * s + attention + head
    got = cell.model.flops_per_sample(c, t)
    assert abs(got - estimate) / estimate < 0.03
    assert encoder_params == 84_934_656


def test_peaks_name_their_source_and_refuse_the_unknown():
    p = peaks.peak("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bytes_s, p.ici_bits_s) == (
        197e12, 819e9, 1600e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="peaks.py"):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_end_to_end_readers_arithmetic():
    run = {"window": window.Window(seconds=10.0, attempted=101,
                                   completed=100, failed=1),
           "samples_per_step": 256, "chips": 4, "flops_per_sample": 23.15e9,
           "peak": peaks.peak("TPU v5 lite"), "setup_s": 35.5}

    def read(name):
        return lookup.metric_reader("e2e_metrics", name)(run)

    assert read("throughput") == 2560.0         # completed steps only
    assert abs(read("mfu_pct")
               - 100 * 2560.0 * 23.15e9 / (4 * 197e12)) < 1e-12
    assert read("setup_s") == 35.5


# ---- the last line -------------------------------------------------------

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 9_000_000_000}


def test_result_line_has_exactly_the_contracted_keys():
    line = json.loads(result.line(
        correct=True, attempted=100, failed=0,
        metrics={"throughput": {"value": 2530.1234, "unit": "samples/s"}},
        device=dict(DEVICE)))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device"]
    assert list(line["device"]) == ["platform", "kind", "count",
                                    "memory_peak_bytes"]
    traced = json.loads(result.line(
        correct=True, attempted=10, failed=0, metrics={},
        device=dict(DEVICE, busy_s=0.8, window_s=0.81),
        breakdown={"device_ops": [["fusion", 0.4]], "idle_gaps": []}))
    assert list(traced)[-1] == "breakdown"
    assert list(traced["device"])[-2:] == ["busy_s", "window_s"]
    assert list(traced["breakdown"]) == ["device_ops", "idle_gaps"]


def test_result_line_refuses_an_extra_key():
    with pytest.raises(ValueError):
        result.line(correct=True, attempted=1, failed=0, metrics={},
                    device=dict(DEVICE, versions="0.9.0"))
    with pytest.raises(ValueError):
        result.line(correct=True, attempted=1, failed=0, device=dict(DEVICE),
                    metrics={"x": {"value": 1, "unit": "s", "why": "no"}})
    with pytest.raises(ValueError):
        result.line(correct=True, attempted=1, failed=0, metrics={},
                    device=dict(DEVICE, busy_s=1.0, window_s=1.0),
                    breakdown={"device_ops": [["op", 0.1]] * 11,
                               "idle_gaps": []})


# ---- the window ----------------------------------------------------------

class _Loss:
    def __init__(self, value, log, i):
        self.value, self.log, self.i = value, log, i

    def asnumpy(self):
        self.log.append(("block", self.i))
        return self.value


def _stepper(log, values=None):
    count = iter(range(10**6))

    def step():
        i = next(count)
        log.append(("step", i))
        v = 1.0 if values is None else values[i]
        if isinstance(v, Exception):
            raise v
        return _Loss(v, log, i)
    return step


def test_window_blocks_on_step_i_minus_two_before_dispatching_step_i():
    log = []
    w = window.run(_stepper(log), steps=5)
    assert (w.attempted, w.completed, w.failed) == (5, 5, 0)
    assert log == [("step", 0), ("step", 1), ("block", 0), ("step", 2),
                   ("block", 1), ("step", 3), ("block", 2), ("step", 4),
                   ("block", 3), ("block", 4)]
    assert len(w.step_call_s) == 5 and len(w.done_at_s) == 5
    assert w.seconds >= w.done_at_s[-1]


def test_window_by_the_clock_drains_what_it_dispatched():
    log = []
    w = window.run(_stepper(log), seconds=0.05)
    assert w.attempted == w.completed > 2 and w.failed == 0
    assert w.seconds >= 0.05
    assert sum(k == "block" for k, _ in log) == w.attempted


def test_window_counts_a_non_finite_loss_and_a_raise_as_failed():
    w = window.run(_stepper([], [1.0, math.nan, 1.0, math.inf]), steps=4)
    assert (w.attempted, w.completed, w.failed) == (4, 2, 2)
    w = window.run(_stepper([], [1.0, 1.0, RuntimeError("boom"), 1.0]),
                   steps=4)
    assert w.attempted == 3 and w.failed == 1 and w.completed == 2
    assert "boom" in w.errors[0]


# ---- one cell end to end, as a rehearsal ---------------------------------

def _run(*argv, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *argv],
        capture_output=True, text=True, cwd=REPO, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def test_without_a_tpu_nothing_is_measured():
    r = _run("--workload", "bert_base_s128", "--seed", "0", "--seconds", "1",
             "--trace", "0")
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_contracted_line_and_no_number(trace, tmp_path):
    r = _run("--workload", "bert_base_s128", "--seed", "3", "--seconds", "1",
             "--trace", trace, "--rehearse",
             env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device"]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 3
    assert last["device"]["platform"] == "cpu"
    kind = "per_layer" if trace == "1" else "end_to_end"
    want = [m["name"] for m in MANIFEST[kind]
            if "bert_base_s128" in m.get("workloads", ["bert_base_s128"])]
    assert list(last["metrics"]) == want
    assert all(m["value"] is None for m in last["metrics"].values())
    assert all(line.startswith("[info] ") for line in lines[:-1])
    checks = json.loads(lines[-2][len("[info] "):])["checks"]
    assert all(checks.values()), checks
