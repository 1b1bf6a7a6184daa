"""startup_time: the nine readers that split `setup_s` (PR 36), on a
hand-made tree of records and on the records of one run on the chip
(`data/startup_bert_base_s128.json`: the `setup_by_phase` info line and
`setup_s` of `--workload bert_base_s128 --trace 1`, one v5e chip, warm
cache, my chip run, PR 36)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path[:0] = [BENCH]

from harness import lookup, startup_time  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

NINE = {
    "import_s": "imports",
    "param_init_s": "models",
    "param_place_s": "one-program step, host side",
    "step_trace_s": "compile cache",
    "step_lower_s": "compile cache",
    "step_backend_s": "compile cache",
    "forward_build_s": "compile cache",
    "first_dispatch_s": "device",
    "setup_outside_program_s": "outside the program",
}


def record(id, name, parent, start, end, seconds=None, **stats):
    return {"id": id, "name": name, "parent": parent, "start": start,
            "end": end, "calls": 1, "stats": stats,
            "seconds": end - start if seconds is None else seconds}


def hand_made():
    """Import with jax inside; 3 parameters initialised in one merged
    record (2.5 s over a span of 4) of which one inside the step's trace
    (a deferred shape), so that record has a second, nested twin; cast;
    place; the forward's and the step's builds; the first dispatch; and
    one phase still open."""
    step = {"program": "mx_train_step", "site": "parallel.spmd_step"}
    fwd = {"program": "forward", "site": "parallel.spmd_forward"}
    return [
        record(0, "mx.setup.import", None, 0.0, 3.0),
        record(1, "mx.setup.import.jax", 0, 0.5, 2.5),
        record(2, "mx.setup.init", None, 4.0, 8.0, seconds=2.5,
               parameters=2, elements=10),
        record(3, "mx.setup.cast", None, 8.0, 8.5),
        record(4, "mx.setup.place", None, 9.0, 10.0, arrays=4, bytes=80),
        record(5, "mx.build.trace", None, 11.0, 12.0, **fwd),
        record(6, "mx.build.lower", None, 12.0, 12.25, **fwd),
        record(7, "mx.build.backend", None, 12.25, 13.0, origin="cache",
               **fwd),
        record(8, "mx.build.trace", None, 20.0, 24.0, **step),
        record(9, "mx.setup.init", 8, 21.0, 21.5, parameters=1,
               elements=2),
        record(10, "mx.build.lower", None, 24.0, 25.0, **step),
        record(11, "mx.build.backend", None, 25.0, 27.0, origin="compiled",
               **step),
        record(12, "mx.step.first_dispatch", None, 27.5, 28.0,
               site="parallel.spmd_step"),
        dict(record(13, "mx.build.trace", None, 30.0, 30.0), end=None),
    ]


def run_of(spans, setup_s, monkeypatch):
    monkeypatch.setattr(startup_time, "_from_the_program",
                        lambda: (spans, {"trace": 9.0, "lower": 2.0,
                                         "backend": 4.0,
                                         "cache_retrieval": 0.5}))
    return {"setup_s": setup_s}


def read_nine(run):
    return {n: lookup.metric_reader("layer_metrics", n)(run) for n in NINE}


def test_self_seconds_and_the_nine_readers_on_a_hand_made_tree(
        monkeypatch, capsys):
    got = read_nine(run_of(hand_made(), 40.0, monkeypatch))
    assert got == {
        "import_s": 3.0,                    # 1.0 of its own + jax's 2.0
        "param_init_s": 2.5 + 0.5 + 0.5,    # both init records and the cast
        "param_place_s": 1.0,
        "step_trace_s": 4.0 - 0.5,          # less the init inside it
        "step_lower_s": 1.0,
        "step_backend_s": 2.0,
        "forward_build_s": 1.0 + 0.25 + 0.75,
        "first_dispatch_s": 0.5,
        # 40 less the parentless records: 3 + 2.5 + .5 + 1 + 2 + 7 + .5
        "setup_outside_program_s": 40.0 - 16.5,
    }
    assert sum(got.values()) == 40.0
    # one info line a run, every closed record on it with its parent, its
    # self-seconds and, on a backend build, its origin; JAX's totals
    lines = [l for l in capsys.readouterr().out.splitlines() if l]
    assert len(lines) == 1 and lines[0].startswith("[info] ")
    report = json.loads(lines[0][len("[info] "):])["setup_by_phase"]
    assert [r["id"] for r in report["spans"]] == list(range(13))
    by_id = {r["id"]: r for r in report["spans"]}
    assert by_id[1]["parent"] == 0 and by_id[9]["parent"] == 8
    assert by_id[0]["self_seconds"] == 1.0
    assert by_id[8]["self_seconds"] == 3.5
    assert by_id[7]["stats"]["origin"] == "cache"
    assert by_id[11]["stats"]["origin"] == "compiled"
    assert report["top_level_s"] == 16.5
    assert report["jax_process_seconds"]["trace"] == 9.0


def test_a_phase_nobody_entered_reads_none_and_the_rest_still_add_up(
        monkeypatch):
    spans = [r for r in hand_made()
             if r["stats"].get("program") != "forward"
             and r["name"] != "mx.step.first_dispatch"]
    got = read_nine(run_of(spans, 40.0, monkeypatch))
    assert got["forward_build_s"] is None
    assert got["first_dispatch_s"] is None
    assert got["setup_outside_program_s"] == 40.0 - 14.0
    assert sum(v for v in got.values() if v is not None) == 40.0


def test_a_program_without_the_records_reads_none_everywhere(monkeypatch,
                                                            capsys):
    monkeypatch.setattr(startup_time, "_from_the_program", lambda: None)
    assert read_nine({"setup_s": 40.0}) == dict.fromkeys(NINE)
    assert capsys.readouterr().out == ""


def test_the_programs_accessors_are_found_or_quietly_missing(monkeypatch):
    spans, totals = startup_time._from_the_program()
    assert spans[0]["name"] == "mx.setup.import"
    assert set(totals) == {"trace", "lower", "backend", "cache_retrieval"}
    from mxnet_tpu.telemetry import tracing

    monkeypatch.delattr(tracing, "startup_spans")
    assert startup_time._from_the_program() is None


def test_the_recorded_run_adds_up_to_its_setup_s(monkeypatch):
    with open(os.path.join(HERE, "data",
                           "startup_bert_base_s128.json")) as f:
        recorded = json.load(f)
    monkeypatch.setattr(
        startup_time, "_from_the_program",
        lambda: (recorded["spans"], recorded["jax_process_seconds"]))
    got = read_nine({"setup_s": recorded["setup_s"]})
    assert all(v is not None and v >= 0.0 for v in got.values()), got
    assert got == pytest.approx(recorded["metrics"], abs=1e-9)
    assert sum(got.values()) == pytest.approx(recorded["setup_s"],
                                              abs=1e-9)
    names = {r["name"] for r in recorded["spans"]}
    # no `mx.setup.import.jax`: the benchmark's reference.py imports jax
    # before the program is imported, so jax's import is the remainder's
    assert names == {"mx.setup.import", "mx.setup.init", "mx.setup.cast",
                     "mx.setup.place", "mx.build.trace", "mx.build.lower",
                     "mx.build.backend", "mx.step.first_dispatch"}
    assert all(r["parent"] is None for r in recorded["spans"])
    # the step's three stages are what step_compile_s had in one number
    assert got["step_trace_s"] + got["step_lower_s"] \
        + got["step_backend_s"] == pytest.approx(
            recorded["step_compile_s"], abs=0.2)
    # JAX's own totals, over every program of the process, hold the
    # program's own stages
    for stage in ("trace", "lower", "backend"):
        own = sum(r["seconds"] for r in recorded["spans"]
                  if r["name"] == "mx.build." + stage)
        assert recorded["jax_process_seconds"][stage] >= own


def test_the_nine_entries_close_per_layer_and_list_no_cells():
    last = MANIFEST["per_layer"][-len(NINE):]
    assert [m["name"] for m in last] == list(NINE)
    for m in last:
        assert m == {"name": m["name"], "unit": "s", "better": "lower",
                     "source": "program_counter", "layer": NINE[m["name"]],
                     "moves": "setup_s"}
    for cell in (w["name"] for w in MANIFEST["workloads"]):
        names = [m["name"] for m in lookup.cell(cell).per_layer]
        assert names[-len(NINE):] == list(NINE)


def test_rehearsal_prints_the_nine_names_with_null_values(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "resnet50_bs256", "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=REPO, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert r.returncode == 0, r.stderr[-2000:]
    metrics = json.loads(r.stdout.strip().splitlines()[-1])["metrics"]
    assert list(metrics)[-len(NINE):] == list(NINE)
    assert all(metrics[n] == {"value": None, "unit": "s"} for n in NINE)
