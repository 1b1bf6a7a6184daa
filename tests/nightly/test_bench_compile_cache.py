"""Compile-cache gate (ref: COMPILE_CACHE.json — ISSUE 7).

The strict enforcement lane for the warm-start bench: a fresh process
with a pre-warmed cache directory must serve its first request >= 3x
faster than a cold one and take its first fused step with ZERO XLA
compiles.  Tier-1 keeps a --no-gate smoke in
tests/test_tools_bench.py; the in-process behavior suite is
tests/test_compile_cache.py.
"""
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _run(cmd, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(cmd, capture_output=True, text=True, cwd=_REPO,
                       timeout=timeout, env=env)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert lines, p.stdout[-2000:]
    return [json.loads(ln) for ln in lines]


def test_bench_compile_cache_gate(tmp_path):
    out = tmp_path / "COMPILE_CACHE.json"
    rows = _run([sys.executable, "tools/bench_compile_cache.py",
                 "--repeats", "3", "--out", str(out)], timeout=600)
    report = rows[-1]
    assert report["gate_ok"] is True
    sv = report["serving"]
    assert sv["speedup"] >= 3.0
    assert sv["cold_xla_compiles"] > 0     # cold really compiled
    assert sv["warm_xla_compiles"] == 0    # warm really did not
    assert sv["warm_disk_hits"] > 0        # ...because the cache served
    fu = report["fused"]
    assert fu["speedup"] >= 1.2
    assert fu["cold_xla_compiles"] > 0
    assert fu["warm_xla_compiles"] == 0 and fu["warm_disk_hits"] > 0
    assert json.loads(out.read_text()) == report
