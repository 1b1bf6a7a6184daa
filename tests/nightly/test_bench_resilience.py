"""Resilience gate (ref: RESILIENCE.json — ISSUE 6).

The strict enforcement lane for the chaos bench: an injected
preemption must resume bit-consistent with an uninterrupted run within
the recovery budget, and a breaker trip must shed (not serve, not
crash) while /healthz stays up.  Tier-1 keeps a --no-gate smoke in
tests/test_tools_bench.py; the in-process behavior suite is
tests/test_resilience.py.
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


def test_bench_resilience_gate(tmp_path):
    out = tmp_path / "RESILIENCE.json"
    rows = _run([sys.executable, "tools/bench_resilience.py",
                 "--out", str(out)], timeout=420)
    report = rows[-1]
    assert report["gate_ok"] is True
    rec = report["recovery"]
    assert rec["resume_bit_consistent"] is True
    assert 0 < rec["recovery_time_to_first_step_s"] < 60.0
    br = report["breaker"]
    assert br["breaker_opened"] and br["breaker_recovered"]
    assert br["requests_dropped_during_trip"] > 0
    assert br["healthz_always_up"] and br["process_survived"]
    # dropped requests were shed by the breaker, and the metric agrees
    assert br["breaker_rejected_metric"] \
        == br["requests_dropped_during_trip"]
    assert json.loads(out.read_text()) == report
