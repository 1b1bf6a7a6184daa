"""Serving throughput gate (ref: SERVING_BENCH.json — ISSUE 1).

A strict perf assertion — batched throughput must beat unbatched at
concurrency >= 8 — belongs in the nightly perf-gate lane, not tier-1:
on a loaded shared CPU the margin is real but the wall-clock is not
deterministic.  Tier-1 still exercises the whole serving stack
in-process via tests/test_serving.py.
"""
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


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


def test_bench_serving_batched_beats_unbatched(tmp_path):
    """ISSUE 1 gate: at concurrency >= 8, server-side batching must
    yield strictly higher throughput than one-launch-per-request, and
    the report must carry QPS, p50/p99, and batch occupancy."""
    out = tmp_path / "SERVING_BENCH.json"
    rows = _run([sys.executable, "tools/bench_serving.py",
                 "--duration", "2.5", "--out", str(out)], timeout=420)
    report = rows[-1]
    assert report["batched_over_unbatched"] > 1.0
    assert report["batched"]["concurrency"] >= 8
    for mode in ("unbatched", "batched"):
        r = report[mode]
        assert r["qps"] > 0 and r["p50_latency_ms"] > 0
        assert r["p99_latency_ms"] >= r["p50_latency_ms"]
        assert 0 < r["batch_occupancy"] <= 1.0
    assert report["batched"]["mean_batch_rows"] > 1.0
    assert json.loads(out.read_text()) == report
