"""Smoke lane for the measurement tooling (bench_all / opperf /
scaling_bench): each harness must produce a parseable JSON row on the
CPU backend.  A speed comes only from a run on the chip."""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(cmd, capture_output=True, text=True, cwd=_REPO,
                       timeout=timeout, env=env)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert lines, p.stdout[-2000:]
    return [json.loads(ln) for ln in lines]


def test_opperf_subset():
    rows = _run([sys.executable, "tools/opperf.py",
                 "--ops", "softmax,FullyConnected",
                 "--repeat", "2", "--number", "3"])
    by_op = {r["op"]: r for r in rows}
    assert set(by_op) == {"softmax", "FullyConnected"}
    for r in rows:
        assert r["eager_us"] > 0 and r["jit_fwd_us"] > 0
        assert r["jit_bwd_us"] > 0


def _load_opperf():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "opperf_under_test", os.path.join(_REPO, "tools", "opperf.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_opperf_gate_flags_baseline_present_now_missing(tmp_path):
    """An op that regresses from working to not-running-at-all (its jit
    column is now None) must be REPORTED by the gate, not silently
    skipped — that's the worst regression class (ADVICE round 5)."""
    opperf = _load_opperf()
    base = {"backend": "cpu", "rows": [
        {"op": "dot", "shape": "s", "jit_fwd_us": 120.0,
         "jit_bwd_us": 150.0},
        {"op": "exp", "shape": "s", "jit_fwd_us": 80.0,
         "jit_bwd_us": 90.0},
    ]}
    bpath = tmp_path / "base.json"
    bpath.write_text(json.dumps(base))
    current = {"backend": "cpu", "rows": [
        # dot's backward no longer runs; forward is fine
        {"op": "dot", "shape": "s", "jit_fwd_us": 125.0,
         "jit_bwd_us": None},
        {"op": "exp", "shape": "s", "jit_fwd_us": 82.0,
         "jit_bwd_us": 91.0},
    ]}
    regressions, compared = opperf.compare(current, str(bpath),
                                           fail_over=1.0)
    assert compared == 4  # the missing column still counts as compared
    assert [(r["op"], r["col"], r["now_us"]) for r in regressions] == \
        [("dot", "jit_bwd_us", None)]
    assert "missing" in regressions[0]["note"]
    # a real slowdown and a missing column are both reported
    current["rows"][1]["jit_fwd_us"] = 400.0
    regressions, _ = opperf.compare(current, str(bpath), fail_over=1.0)
    assert {(r["op"], r["col"]) for r in regressions} == \
        {("dot", "jit_bwd_us"), ("exp", "jit_fwd_us")}


def test_opperf_gate_flags_baseline_row_entirely_missing(tmp_path):
    """An op whose ROW vanished from the current sweep (spec dropped,
    crashed before measuring) is the same working-to-not-running class
    as a missing column — reported, never a silent skip.  A deliberate
    subset run opts out via expect_all_baseline_rows=False."""
    opperf = _load_opperf()
    base = {"backend": "cpu", "rows": [
        {"op": "dot", "shape": "s", "jit_fwd_us": 120.0,
         "jit_bwd_us": 150.0},
        {"op": "exp", "shape": "s", "jit_fwd_us": 80.0},
    ]}
    bpath = tmp_path / "base.json"
    bpath.write_text(json.dumps(base))
    current = {"backend": "cpu", "rows": [
        {"op": "exp", "shape": "s", "jit_fwd_us": 82.0},
    ]}
    regressions, _ = opperf.compare(current, str(bpath), fail_over=1.0)
    assert {(r["op"], r["col"]) for r in regressions} == \
        {("dot", "jit_fwd_us"), ("dot", "jit_bwd_us")}
    assert all(r["now_us"] is None and r["row_missing"]
               for r in regressions)
    regressions, _ = opperf.compare(current, str(bpath), fail_over=1.0,
                                    expect_all_baseline_rows=False)
    assert regressions == []


def test_bench_serving_smoke(tmp_path):
    """CLI smoke only: the load generator runs and emits a well-formed
    report.  The strict batched>unbatched throughput gate lives in
    tests/nightly/test_bench_serving.py (perf lane)."""
    out = tmp_path / "SERVING_BENCH.json"
    rows = _run([sys.executable, "tools/bench_serving.py", "--no-gate",
                 "--duration", "0.4", "--repeats", "1",
                 "--max-batch-size", "4", "--in-units", "16",
                 "--hidden", "32", "--out-units", "8",
                 "--out", str(out)], timeout=420)
    report = rows[-1]
    for mode in ("unbatched", "batched"):
        r = report[mode]
        assert r["qps"] > 0 and r["p50_latency_ms"] > 0
        assert r["p99_latency_ms"] >= r["p50_latency_ms"]
    assert report["batched"]["concurrency"] >= 8
    assert json.loads(out.read_text()) == report


def test_bench_fused_step_smoke(tmp_path):
    """CLI smoke only: the fused-step bench runs and emits a
    well-formed report with the compile count.  The strict
    fused>=1.2x-eager gate lives in tests/nightly/
    test_bench_fused_step.py (perf lane)."""
    out = tmp_path / "FUSED_BENCH.json"
    rows = _run([sys.executable, "tools/bench_fused_step.py",
                 "--no-gate", "--params", "8", "--steps", "4",
                 "--out", str(out)], timeout=420)
    report = rows[-1]
    assert report["metric"] == "fused_step_speedup"
    assert set(report["sizes"]) == {"8"}
    for row in report["sizes"].values():
        assert row["eager_ms_per_step"] > 0
        assert row["fused_ms_per_step"] > 0
        # the no-recompile invariant is NOT noise-prone — a smoke run
        # must already hold it (one executable per size, lr change
        # included)
        assert row["fused_compiles"] == 1
    assert report["gate_params"] == 8
    assert json.loads(out.read_text()) == report


def test_bench_all_mnist_smoke():
    rows = _run([sys.executable, "bench_all.py", "--cpu-smoke",
                 "--config", "mnist_mlp"])
    assert rows[-1]["metric"] == "mnist_mlp_train_throughput"
    assert rows[-1]["value"] > 0
    # every row names the device it ran on: a CPU number cannot pass
    # for a chip number
    assert (rows[-1]["platform"], rows[-1]["device_kind"],
            rows[-1]["n_devices"]) == ("cpu", "cpu", 1)
    assert rows[-1]["variant"] == "cpu_smoke"


@pytest.mark.slow  # 12s CLI smoke of a tool the nightly spmd stage
# already runs for real (scaling_bench --spmd --phases) — runs nightly
def test_scaling_bench_single_proc():
    """CLI smoke on the SPMD path (the unified spine — ISSUE 9) with
    per-phase attribution; the multi-process sweep, the loss-parity
    gate, and the replica-path comparison live in the run_nightly spmd
    stage."""
    rows = _run([sys.executable, "tools/scaling_bench.py",
                 "--model", "resnet18", "--procs", "1", "--steps", "2",
                 "--warmup", "1", "--batch-per-device", "2",
                 "--image-size", "32", "--spmd", "--phases",
                 "--out", "/tmp/scaling_test.json"])
    assert rows[-1]["processes"] == 1
    assert rows[-1]["efficiency_vs_1proc"] == 1.0
    assert rows[-1]["path"] == "spmd"
    # attribution present (collected after the timed window)
    assert rows[-1]["phase_seconds"].get("spmd-step", {}).get("count")


def test_bench_resilience_smoke(tmp_path):
    """CLI smoke only: the resilience bench runs both scenarios and
    emits a well-formed report.  The strict gate (bit-consistent
    resume, breaker opened+recovered, healthz up) lives in
    tests/nightly/test_bench_resilience.py."""
    out = tmp_path / "RESILIENCE.json"
    rows = _run([sys.executable, "tools/bench_resilience.py",
                 "--no-gate", "--steps", "4", "--preempt-at", "3",
                 "--trip-requests", "8", "--out", str(out)],
                timeout=420)
    report = rows[-1]
    assert report["bench"] == "resilience"
    rec = report["recovery"]
    assert rec["recovery_time_to_first_step_s"] > 0
    assert rec["preempted_checkpoint"].startswith("step-")
    br = report["breaker"]
    assert br["requests_during_trip"] == 8
    assert br["requests_failed_pre_trip"] \
        + br["requests_dropped_during_trip"] == 8
    assert json.loads(out.read_text()) == report


def test_bench_compile_cache_smoke(tmp_path):
    """CLI smoke only: the warm-start bench runs a cold/warm
    subprocess pair and emits a well-formed report.  One scenario at
    tiny sizes — tier-1 runs near its wall-clock cap; the strict
    both-scenario >=3x-speedup / zero-warm-compiles gate lives in
    tests/nightly/test_bench_compile_cache.py."""
    out = tmp_path / "COMPILE_CACHE.json"
    rows = _run([sys.executable, "tools/bench_compile_cache.py",
                 "--no-gate", "--scenarios", "fused",
                 "--params", "4", "--fused-units", "8",
                 "--repeats", "1", "--out", str(out)], timeout=420)
    report = rows[-1]
    assert report["bench"] == "compile_cache"
    assert "serving" not in report  # subset run stays a subset
    r = report["fused"]
    assert r["cold_first_step_s"] > 0 and r["warm_first_step_s"] > 0
    # the structural invariants hold even at smoke sizes: cold
    # compiled, warm did not (it loaded from disk instead)
    assert r["cold_xla_compiles"] > 0
    assert r["warm_xla_compiles"] == 0
    assert r["warm_disk_hits"] > 0
    assert json.loads(out.read_text()) == report
