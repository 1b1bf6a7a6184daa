"""CI-style runner for the nightly tier (ref: the reference's nightly
Jenkins lane): large-array boundary tests + checkpoint backwards
compatibility.  Writes NIGHTLY.json with the tally.

    python tools/run_nightly.py [--out NIGHTLY.json]

Memory: the large-array lane peaks around ~8GB host RAM (int8 arrays
crossing the 2^31-element boundary).  Runtime: minutes, dominated by
whole-array reductions on one core.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _quant_checks(sweep, base_parity=None, quant_parity=None, procs=2):
    """The quantized-lane gates over a merged SCALING sweep: wire
    bytes of the int8 rows' sharded collectives <= 0.30x the fp32
    rows' (the 1 byte/elem + scales budget), loss within 1e-3
    relative of the fp32 lane (error feedback is doing its job), and
    exposed comm (comm_stall) under overlap no worse than the
    un-overlapped lane.  Compares the ``procs``-process rows — the
    1-proc mesh moves no wire bytes.

    Loss parity is judged on the PARITY-stage losses when both lanes
    ran it (pinned seed + pinned GLOBAL batch — the two lanes then
    differ by the wire encoding alone); the sweep rows' overfit-run
    losses ride along informationally only, because a 3-step resnet
    overfit sits on the steep part of the curve where a sub-1e-3
    parameter perturbation legitimately moves the loss percents."""
    base = next((r for r in sweep if r.get("processes") == procs
                 and r.get("path") == "spmd"), None)
    q = next((r for r in sweep if r.get("processes") == procs
              and str(r.get("path", "")).startswith("spmd-")), None)
    if base is None or q is None:
        return {"ok": False, "note": "missing spmd/spmd-int8 rows"}

    def wire(row):
        wb = row.get("collective_wire_bytes") or {}
        return sum(v for k, v in wb.items()
                   if k.startswith(("reduce-scatter", "all-gather")))

    out = {"paths": [base["path"], q["path"]], "processes": procs}
    bw, qw = wire(base), wire(q)
    out["wire_bytes"] = {base["path"]: bw, q["path"]: qw}
    out["wire_ratio"] = round(qw / bw, 4) if bw else None
    out["wire_ok"] = bool(bw and qw and qw <= 0.30 * bw)
    sl = abs(q["loss"] - base["loss"]) / max(abs(base["loss"]), 1e-6)
    out["sweep_loss_rel_diff"] = round(sl, 6)
    bl = (base_parity or {}).get("losses") or []
    ql = (quant_parity or {}).get("losses") or []
    if bl and ql and len(bl) == len(ql):
        lp = max(abs(a - b) / max(abs(a), 1e-6)
                 for a, b in zip(bl, ql))
        out["parity_losses"] = {"fp32": bl, "quant": ql}
        out["loss_rel_diff"] = round(lp, 6)
        out["loss_parity_ok"] = (lp <= 1e-3
                                 and bool((quant_parity or {}).get("ok")))
    else:
        out["loss_rel_diff"] = round(sl, 6)
        out["loss_parity_ok"] = sl <= 1e-3
    bs = float(base.get("comm_stall_s") or 0.0)
    qs = float(q.get("comm_stall_s") or 0.0)
    out["comm_stall_s"] = {base["path"]: bs, q["path"]: qs}
    out["comm_stall_ok"] = qs <= bs + 1e-3
    out["efficiency_2proc"] = q.get("efficiency_vs_1proc")
    out["ok"] = (out["wire_ok"] and out["loss_parity_ok"]
                 and out["comm_stall_ok"])
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(_REPO, "NIGHTLY.json"))
    ap.add_argument("--timeout", type=float, default=3600.0)
    args = ap.parse_args()

    env = dict(os.environ, MXNET_NIGHTLY="1")
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/nightly", "-v",
         "--tb=line"],
        capture_output=True, text=True, timeout=args.timeout, cwd=_REPO,
        env=env)
    out = p.stdout
    cases = dict(re.findall(
        r"tests/nightly/\S+::(\S+)\s+(PASSED|FAILED|SKIPPED|ERROR)", out))
    tally = {k: int(m.group(1)) if (m := re.search(rf"(\d+) {k}", out))
             else 0 for k in ("passed", "failed", "skipped")}
    artifact = {"when": time.strftime("%Y-%m-%d %H:%M:%S"),
                "duration_s": round(time.time() - t0, 1),
                "returncode": p.returncode, **tally, "cases": cases}

    # op-level perf regression gate (round-4 verdict item #4): re-run
    # the CPU opperf sweep and fail the nightly on a sustained 2x op
    # slowdown vs the committed baseline (thresholds calibrated to the
    # 1-core box's timer noise — see tools/opperf.py compare()).
    baseline = os.path.join(_REPO, "OPPERF.json")
    cpu_env = dict(env, JAX_PLATFORMS="cpu")
    opperf_rc = None
    if os.path.exists(baseline):
        try:
            q = subprocess.run(
                [sys.executable, "tools/opperf.py",
                 "--against", baseline, "--fail-over", "1.0"],
                capture_output=True, text=True, timeout=1800, cwd=_REPO,
                env=cpu_env)
            opperf_rc = q.returncode
            artifact["opperf_gate"] = {
                "returncode": q.returncode,
                "tail": "\n".join(q.stdout.splitlines()[-2:]),
                # keep the crash trail: a non-regression failure
                # (import error, spec raising) surfaces only on stderr
                "stderr_tail": "\n".join(q.stderr.splitlines()[-8:])}
        except subprocess.TimeoutExpired:
            opperf_rc = -1
            artifact["opperf_gate"] = {"returncode": -1,
                                       "note": "timed out"}

    # fused-step artifact refresh (ISSUE 3): rewrite FUSED_BENCH.json
    # next to the BENCH_*.json trajectory and record the fused-vs-eager
    # ratio.  --no-gate: the strict >=1.2x enforcement already ran once
    # above via tests/nightly/test_bench_fused_step.py (benching the
    # gate twice per nightly would double the wall clock and let two
    # noisy readings disagree); a non-zero rc here means the harness
    # itself broke, which still fails the nightly.
    fused_rc = None
    try:
        fb = subprocess.run(
            [sys.executable, "tools/bench_fused_step.py", "--no-gate",
             "--params", "10,100,500",
             "--out", os.path.join(_REPO, "FUSED_BENCH.json")],
            capture_output=True, text=True, timeout=1200, cwd=_REPO,
            env=cpu_env)
        fused_rc = fb.returncode
        gate = {"returncode": fb.returncode,
                "stderr_tail": "\n".join(fb.stderr.splitlines()[-6:])}
        try:
            rep = json.loads([ln for ln in fb.stdout.splitlines()
                              if ln.startswith("{")][-1])
            gate["speedup_at_gate"] = rep["speedup_at_gate"]
            gate["fused_over_eager"] = {
                n: r["speedup"] for n, r in rep["sizes"].items()}
        except (IndexError, ValueError, KeyError):
            pass
        artifact["fused_step_bench"] = gate
    except subprocess.TimeoutExpired:
        fused_rc = -1
        artifact["fused_step_bench"] = {"returncode": -1,
                                       "note": "timed out"}

    # trace integrity gate: generate a real training trace through the
    # telemetry layer and validate it (spans present, events well-formed,
    # counter lanes monotone, flow/parent links resolve)
    trace_rc = None
    try:
        r = subprocess.run(
            [sys.executable, "tools/trace_report.py", "--selftest"],
            capture_output=True, text=True, timeout=600, cwd=_REPO,
            env=cpu_env)
        trace_rc = r.returncode
        artifact["trace_report"] = {
            "returncode": r.returncode,
            "tail": "\n".join(r.stdout.splitlines()[-3:]),
            "stderr_tail": "\n".join(r.stderr.splitlines()[-8:])}
    except subprocess.TimeoutExpired:
        trace_rc = -1
        artifact["trace_report"] = {"returncode": -1,
                                    "note": "timed out"}

    # static-analysis gate (ISSUE 4): lint the framework against the
    # committed baseline; --check also fails on stale entries so the
    # baseline ratchets down.  MXLINT.json records per-rule counts —
    # the trajectory tracked across PRs.
    mxlint_rc = None
    try:
        lr = subprocess.run(
            [sys.executable, "tools/mxlint.py", "mxnet_tpu",
             "--baseline", "MXLINT_BASELINE.json", "--json", "--check",
             "--out", os.path.join(_REPO, "MXLINT.json")],
            capture_output=True, text=True, timeout=300, cwd=_REPO,
            env=cpu_env)
        mxlint_rc = lr.returncode
        gate = {"returncode": lr.returncode,
                "stderr_tail": "\n".join(lr.stderr.splitlines()[-6:])}
        try:
            rep = json.loads(lr.stdout)
            gate["counts"] = rep["counts"]
            gate["new_per_rule"] = rep["new_per_rule"]
            # the full per-rule trajectory incl. the mxflow rules
            # (MX008–MX012): baselined counts are what ratchets down
            # across PRs, so the nightly records them too
            gate["baselined_per_rule"] = rep["baselined_per_rule"]
            gate["stale_baseline"] = rep["counts"]["stale_baseline"]
        except (ValueError, KeyError):
            pass
        # cross-artifact drift (the cheap seventh pass): telemetry
        # instruments vs docs/observability.md, chaos sites vs
        # docs/resilience.md — doc drift fails the nightly like a
        # stale env_vars.md does
        dr = subprocess.run(
            [sys.executable, "tools/mxlint.py", "--drift"],
            capture_output=True, text=True, timeout=120, cwd=_REPO,
            env=cpu_env)
        gate["drift_returncode"] = dr.returncode
        gate["drift_tail"] = "\n".join(dr.stdout.splitlines()[-3:])
        if mxlint_rc == 0 and dr.returncode != 0:
            mxlint_rc = dr.returncode
        artifact["mxlint"] = gate
    except subprocess.TimeoutExpired:
        mxlint_rc = -1
        artifact["mxlint"] = {"returncode": -1, "note": "timed out"}

    # dynamic-analysis gate (ISSUE 5): the threaded test subset under
    # MXNET_SAN=1 — lock-order cycles, lockset races on tracked caches,
    # recompile storms all fail the run (via the mxsan pytest plugin)
    # and land in MXSAN.json.  The same subset runs WITHOUT the
    # sanitizer first so the recorded overhead ratio is ground truth
    # (acceptance: <3x wall-clock).
    san_rc = None
    subset = ["tests/test_mxsan.py", "tests/test_mxlint.py",
              "tests/test_serving.py", "tests/test_telemetry_serving.py"]
    try:
        tb = time.time()
        base = subprocess.run(
            [sys.executable, "-m", "pytest", *subset, "-q",
             "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=1800, cwd=_REPO,
            env=cpu_env)
        base_s = time.time() - tb
        san_out = os.path.join(_REPO, "MXSAN.json")
        if os.path.exists(san_out):
            os.remove(san_out)  # never report a previous run's counts
        ts = time.time()
        sr = subprocess.run(
            [sys.executable, "-m", "pytest", *subset, "-q",
             "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=1800, cwd=_REPO,
            env=dict(cpu_env, MXNET_SAN="1", MXNET_SAN_OUT=san_out))
        san_s = time.time() - ts
        ratio = round(san_s / max(base_s, 1e-9), 2)
        gate = {"returncode_base": base.returncode,
                "returncode_san": sr.returncode,
                "wall_base_s": round(base_s, 1),
                "wall_san_s": round(san_s, 1),
                "overhead_ratio": ratio,
                "tail": "\n".join(sr.stdout.splitlines()[-2:])}
        # the gate reads the REPORT, not just return codes: a
        # violation recorded outside any test window (import time, a
        # daemon thread after the last teardown) exits pytest 0 but
        # still lands in MXSAN.json; a missing report means the
        # sanitized session died before sessionfinish
        report_violations = None
        try:
            with open(san_out) as f:
                gate["counts"] = json.load(f)["counts"]
            report_violations = gate["counts"].get("violations")
        except (OSError, ValueError, KeyError):
            gate["note"] = "MXSAN.json missing/unreadable"
        artifact["mxsan"] = gate
        san_rc = 0 if (base.returncode == 0 and sr.returncode == 0
                       and report_violations == 0
                       and ratio < 3.0) else 1
    except subprocess.TimeoutExpired:
        san_rc = -1
        artifact["mxsan"] = {"returncode": -1, "note": "timed out"}

    # chaos gate (ISSUE 6): the slow-marked chaos tests (process-pool
    # worker death) — tier-1 excludes them for wall-clock, the fault
    # must still be exercised every night.  The strict resilience
    # bench moved into the elastic stage below (ISSUE 15), which owns
    # the RESILIENCE.json refresh so one nightly writes it once.
    resil_rc = None
    try:
        sl = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_resilience.py",
             "-q", "-m", "slow", "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=600, cwd=_REPO,
            env=cpu_env)
        resil_rc = sl.returncode
        artifact["resilience"] = {
            "slow_chaos_returncode": sl.returncode,
            "slow_chaos_tail": "\n".join(sl.stdout.splitlines()[-1:])}
    except subprocess.TimeoutExpired:
        resil_rc = -1
        artifact["resilience"] = {"returncode": -1, "note": "timed out"}

    # elastic gate (ISSUE 15): the slow multi-process elastic e2e
    # (supervisor recovers a killed AND a hung rank in shrink and
    # replace mode, loss parity vs an uninterrupted twin) plus the
    # STRICT resilience bench with the elastic matrix — RESILIENCE.json
    # is the tracked artifact and perf_compare gates it with strict
    # lanes (a recovery regression is never grandfathered).  Runs
    # BEFORE perf-compare so the artifact it diffs is fresh.
    elastic_rc = None
    try:
        esl = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_elastic.py",
             "-q", "-m", "slow", "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=1200, cwd=_REPO,
            env=cpu_env)
        er = subprocess.run(
            [sys.executable, "tools/bench_resilience.py", "--elastic",
             "--out", os.path.join(_REPO, "RESILIENCE.json")],
            capture_output=True, text=True, timeout=1800, cwd=_REPO,
            env=cpu_env)
        elastic_rc = er.returncode if er.returncode != 0 \
            else esl.returncode
        gate = {"returncode": er.returncode,
                "slow_tests_returncode": esl.returncode,
                "slow_tests_tail":
                    "\n".join(esl.stdout.splitlines()[-1:]),
                "stderr_tail": "\n".join(er.stderr.splitlines()[-6:])}
        try:
            rep = json.loads([ln for ln in er.stdout.splitlines()
                              if ln.startswith("{")][-1])
            gate["gate_ok"] = rep["gate_ok"]
            gate["recovery_time_to_first_step_s"] = \
                rep["recovery"]["recovery_time_to_first_step_s"]
            gate["resume_bit_consistent"] = \
                rep["recovery"]["resume_bit_consistent"]
            gate["healthz_always_up"] = \
                rep["breaker"]["healthz_always_up"]
            gate["elastic_ok"] = rep["elastic"]["ok"]
            gate["elastic_mttr_s"] = {
                name: run.get("mttr_s")
                for name, run in rep["elastic"]["runs"].items()}
        except (IndexError, ValueError, KeyError):
            pass
        artifact["elastic"] = gate
    except subprocess.TimeoutExpired:
        elastic_rc = -1
        artifact["elastic"] = {"returncode": -1, "note": "timed out"}

    # compile-cache gate (ISSUE 7): the warm-start bench under its
    # strict gate — a fresh process with a pre-warmed cache dir must
    # serve >=3x faster than cold with zero XLA compiles (subprocess
    # cold/warm pairs; COMPILE_CACHE.json is the tracked artifact).
    # The slow-marked cross-process tests (warm subprocess, corrupt
    # quarantine under chaos) run here too — tier-1 excludes them for
    # wall-clock.
    cc_rc = None
    try:
        csl = subprocess.run(
            [sys.executable, "-m", "pytest",
             "tests/test_compile_cache.py", "-q", "-m", "slow",
             "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env=cpu_env)
        cb = subprocess.run(
            [sys.executable, "tools/bench_compile_cache.py",
             "--repeats", "3",
             "--out", os.path.join(_REPO, "COMPILE_CACHE.json")],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env=cpu_env)
        cc_rc = cb.returncode if cb.returncode != 0 else csl.returncode
        gate = {"returncode": cb.returncode,
                "slow_tests_returncode": csl.returncode,
                "slow_tests_tail":
                    "\n".join(csl.stdout.splitlines()[-1:]),
                "stderr_tail": "\n".join(cb.stderr.splitlines()[-6:])}
        try:
            rep = json.loads([ln for ln in cb.stdout.splitlines()
                              if ln.startswith("{")][-1])
            gate["serving_speedup"] = rep["serving"]["speedup"]
            gate["fused_speedup"] = rep["fused"]["speedup"]
            gate["warm_xla_compiles"] = (
                rep["serving"]["warm_xla_compiles"]
                + rep["fused"]["warm_xla_compiles"])
            gate["gate_ok"] = rep["gate_ok"]
        except (IndexError, ValueError, KeyError):
            pass
        artifact["compile_cache"] = gate
    except subprocess.TimeoutExpired:
        cc_rc = -1
        artifact["compile_cache"] = {"returncode": -1,
                                     "note": "timed out"}

    # unified-SPMD gate (ISSUE 9): the scaling harness on BOTH step
    # paths over real multi-process (gloo) transport.  Hard gates:
    # the fixed-global-batch loss-parity stage inside the spmd sweep
    # (rc != 0 = the curves diverged — a gradient-averaging or data-
    # sharding bug), and 2-process efficiency on the SPMD path must
    # not fall below the per-replica path's (0.05 absolute slack for
    # the 1-core box's timer noise).  SCALING.json (spmd sweep, with
    # per-phase attribution) is the tracked artifact; the slow-marked
    # multi-process spmd tests run here too.
    spmd_rc = None
    try:
        ssl = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_spmd_step.py",
             "-q", "-m", "slow", "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=1200, cwd=_REPO,
            env=cpu_env)
        rb = subprocess.run(
            [sys.executable, "tools/scaling_bench.py", "--procs", "1,2",
             "--path", "replica", "--steps", "3", "--no-parity",
             "--out", os.path.join(_REPO, "SCALING_replica.json")],
            capture_output=True, text=True, timeout=1800, cwd=_REPO,
            env=cpu_env)
        sb = subprocess.run(
            [sys.executable, "tools/scaling_bench.py", "--procs", "1,2",
             "--spmd", "--phases", "--steps", "3",
             "--out", os.path.join(_REPO, "SCALING.json")],
            capture_output=True, text=True, timeout=1800, cwd=_REPO,
            env=cpu_env)
        # quantized lane (ISSUE 18): the SAME spmd sweep under
        # MXNET_COMM_QUANT=int8 + gradient-ready overlap; its rows
        # merge into SCALING.json beside the raw rows, and the quant
        # checks below gate wire bytes (<=0.30x), loss parity vs the
        # fp32 lane (<=1e-3), and that overlap keeps exposed comm
        # (comm_stall) no worse than the un-overlapped lane
        qb = subprocess.run(
            [sys.executable, "tools/scaling_bench.py", "--procs", "1,2",
             "--spmd", "--phases", "--steps", "3", "--quant", "int8",
             "--overlap",
             "--out", os.path.join(_REPO, "SCALING_quant.json")],
            capture_output=True, text=True, timeout=1800, cwd=_REPO,
            env=cpu_env)
        gate = {"returncode_replica": rb.returncode,
                "returncode_spmd": sb.returncode,
                "returncode_quant": qb.returncode,
                "slow_tests_returncode": ssl.returncode,
                "slow_tests_tail":
                    "\n".join(ssl.stdout.splitlines()[-1:]),
                "stderr_tail": "\n".join(sb.stderr.splitlines()[-6:])}
        eff_ok = True
        quant_ok = True
        try:
            def eff2(path):
                with open(path) as f:
                    rep = json.load(f)
                row = [r for r in rep["sweep"] if r["processes"] == 2]
                return row[0]["efficiency_vs_1proc"] if row else None

            rep_eff = eff2(os.path.join(_REPO, "SCALING_replica.json"))
            spmd_eff = eff2(os.path.join(_REPO, "SCALING.json"))
            gate["efficiency_2proc"] = {"replica": rep_eff,
                                        "spmd": spmd_eff}
            if rep_eff is not None and spmd_eff is not None:
                eff_ok = spmd_eff + 0.05 >= rep_eff
            gate["efficiency_ok"] = eff_ok
            with open(os.path.join(_REPO, "SCALING.json")) as f:
                scaling = json.load(f)
            gate["loss_parity"] = scaling.get("parity", {}).get("ok")
            with open(os.path.join(_REPO, "SCALING_quant.json")) as f:
                qrep = json.load(f)
            scaling["sweep"].extend(qrep.get("sweep", []))
            quant = _quant_checks(scaling["sweep"],
                                  scaling.get("parity"),
                                  qrep.get("parity"))
            scaling["quant"] = quant
            with open(os.path.join(_REPO, "SCALING.json"), "w") as f:
                json.dump(scaling, f, indent=1)
            gate["quant"] = quant
            quant_ok = bool(quant.get("ok"))
        except (OSError, ValueError, KeyError, IndexError):
            gate["note"] = "sweep artifacts unreadable"
        artifact["spmd_scaling"] = gate
        spmd_rc = 0 if (ssl.returncode == 0 and rb.returncode == 0
                        and sb.returncode == 0 and qb.returncode == 0
                        and eff_ok and quant_ok) else 1
    except subprocess.TimeoutExpired:
        spmd_rc = -1
        artifact["spmd_scaling"] = {"returncode": -1,
                                    "note": "timed out"}

    # heavy integration smokes: the slow-marked model-zoo / example /
    # layout / detection / dist / fused-resnet / tool-smoke tests
    # excluded from tier-1 for wall-clock (tier-1 sits just under the
    # 870s cap) — the coverage must still run every night
    heavy_rc = None
    try:
        hv = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_gluon.py",
             "tests/test_examples.py", "tests/test_layout.py",
             "tests/test_detection.py", "tests/test_dist.py",
             "tests/test_fused_resnet.py", "tests/test_tools_bench.py",
             "-q", "-m", "slow", "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=1800, cwd=_REPO,
            env=cpu_env)
        heavy_rc = hv.returncode
        artifact["heavy_integration"] = {
            "returncode": hv.returncode,
            "tail": "\n".join(hv.stdout.splitlines()[-1:])}
    except subprocess.TimeoutExpired:
        heavy_rc = -1
        artifact["heavy_integration"] = {"returncode": -1,
                                         "note": "timed out"}

    # mxprof stage (ISSUE 10): the slow attribution tests (anything
    # spawning worker processes — the scaling_bench --phases e2e) run
    # here; tier-1 keeps the fast unit/gate coverage
    mxprof_rc = None
    try:
        mp = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_mxprof.py",
             "-q", "-m", "slow", "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env=cpu_env)
        mxprof_rc = mp.returncode
        artifact["mxprof"] = {
            "returncode": mp.returncode,
            "tail": "\n".join(mp.stdout.splitlines()[-1:])}
    except subprocess.TimeoutExpired:
        mxprof_rc = -1
        artifact["mxprof"] = {"returncode": -1, "note": "timed out"}

    # health stage (ISSUE 11): the slow mxhealth e2e (2-proc straggler
    # detection on merged traces, alert-engine soak, real serving p99
    # breach) plus the strict known-answer health run — HEALTH.json is
    # the tracked artifact and perf_compare gates it with STRICT lanes
    # (a broken detection path is never grandfathered)
    health_rc = None
    try:
        hsl = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_mxhealth.py",
             "-q", "-m", "slow", "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env=cpu_env)
        hr = subprocess.run(
            [sys.executable, "tools/health_report.py",
             "--out", os.path.join(_REPO, "HEALTH.json")],
            capture_output=True, text=True, timeout=600, cwd=_REPO,
            env=cpu_env)
        health_rc = hr.returncode if hr.returncode != 0 \
            else hsl.returncode
        gate = {"returncode": hr.returncode,
                "slow_tests_returncode": hsl.returncode,
                "slow_tests_tail":
                    "\n".join(hsl.stdout.splitlines()[-1:]),
                "stderr_tail": "\n".join(hr.stderr.splitlines()[-6:])}
        try:
            rep = json.loads([ln for ln in hr.stdout.splitlines()
                              if ln.startswith("{")][-1])
            gate["gate_ok"] = rep["gate_ok"]
            gate["stages"] = rep["stages"]
        except (IndexError, ValueError, KeyError):
            pass
        artifact["health"] = gate
    except subprocess.TimeoutExpired:
        health_rc = -1
        artifact["health"] = {"returncode": -1, "note": "timed out"}

    # triage stage (ISSUE 13): the deep-capture e2e (a REAL firing
    # alert triggers one rate-limited jax.profiler capture whose
    # artifact records the rule and step) and the perf_compare
    # attribution smoke (a synthetic regressed artifact must produce a
    # suspects ranking naming the seeded phase).  Runs BEFORE the
    # perf-compare stage: if attribution is broken, the gate below
    # would fail mutely again.
    triage_rc = None
    try:
        tg = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_mxtriage.py",
             "-q", "-m", "slow", "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env=cpu_env)
        triage_rc = tg.returncode
        artifact["triage"] = {
            "returncode": tg.returncode,
            "tail": "\n".join(tg.stdout.splitlines()[-1:])}
    except subprocess.TimeoutExpired:
        triage_rc = -1
        artifact["triage"] = {"returncode": -1, "note": "timed out"}

    # goodput stage (ISSUE 14): the slow mxgoodput e2e (multi-process
    # chaos known-answer run) plus the strict goodput report —
    # GOODPUT.json is the tracked artifact and perf_compare gates it
    # with STRICT lanes (a goodput ratio is never grandfathered).
    # Runs BEFORE perf-compare so the artifact it diffs is fresh.
    goodput_rc = None
    try:
        gsl = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_mxgoodput.py",
             "-q", "-m", "slow", "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env=cpu_env)
        gr = subprocess.run(
            [sys.executable, "tools/goodput_report.py",
             "--out", os.path.join(_REPO, "GOODPUT.json")],
            capture_output=True, text=True, timeout=600, cwd=_REPO,
            env=cpu_env)
        goodput_rc = gr.returncode if gr.returncode != 0 \
            else gsl.returncode
        gate = {"returncode": gr.returncode,
                "slow_tests_returncode": gsl.returncode,
                "slow_tests_tail":
                    "\n".join(gsl.stdout.splitlines()[-1:]),
                "stderr_tail": "\n".join(gr.stderr.splitlines()[-6:])}
        try:
            rep = json.loads([ln for ln in gr.stdout.splitlines()
                              if ln.startswith("{")][-1])
            gate["gate_ok"] = rep["gate_ok"]
            gate["stages"] = rep["stages"]
        except (IndexError, ValueError, KeyError):
            pass
        artifact["goodput"] = gate
    except subprocess.TimeoutExpired:
        goodput_rc = -1
        artifact["goodput"] = {"returncode": -1, "note": "timed out"}

    # autotune stage (ISSUE 16): the slow mxtune e2e tests (subprocess
    # boot-tuned proof, CLI quick sweep) plus a quick bounded sweep on
    # both gate scenarios refreshing AUTOTUNE.json — the tracked
    # artifact perf_compare gates with STRICT lanes, so a stored winner
    # that regresses below the measured default fails the nightly.
    # Runs BEFORE perf-compare so the artifact it diffs is fresh.
    autotune_rc = None
    try:
        asl = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_autotune.py",
             "-q", "-m", "slow", "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env=cpu_env)
        at = subprocess.run(
            [sys.executable, "tools/autotune.py", "--quick",
             "--out", os.path.join(_REPO, "AUTOTUNE.json")],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env=cpu_env)
        autotune_rc = at.returncode if at.returncode != 0 \
            else asl.returncode
        gate = {"returncode": at.returncode,
                "slow_tests_returncode": asl.returncode,
                "slow_tests_tail":
                    "\n".join(asl.stdout.splitlines()[-1:]),
                "stderr_tail": "\n".join(at.stderr.splitlines()[-6:])}
        try:
            rep = json.loads([ln for ln in at.stdout.splitlines()
                              if ln.startswith("{")][-1])
            gate["gate_ok"] = rep["gate_ok"]
            gate["scenarios"] = rep["scenarios"]
        except (IndexError, ValueError, KeyError):
            pass
        artifact["autotune"] = gate
    except subprocess.TimeoutExpired:
        autotune_rc = -1
        artifact["autotune"] = {"returncode": -1, "note": "timed out"}

    # blackbox stage (ISSUE 17): the slow crash-forensics e2e (a
    # supervised chaos kill must yield bundles from every path — the
    # dying rank's own, the survivor's peer_failed, the supervisor
    # scrape — and a correctly-attributed incident) plus the strict
    # postmortem known-answer selftest refreshing INCIDENT.json — the
    # tracked artifact perf_compare gates with STRICT lanes (a
    # first-failure attribution that degrades to 'unknown' is never
    # grandfathered).  Runs BEFORE perf-compare so the artifact it
    # diffs is fresh.
    blackbox_rc = None
    try:
        bsl = subprocess.run(
            [sys.executable, "-m", "pytest",
             "tests/test_mxblackbox.py", "-q", "-m", "slow",
             "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=1200, cwd=_REPO,
            env=cpu_env)
        br = subprocess.run(
            [sys.executable, "tools/postmortem.py", "--selftest",
             "--out", os.path.join(_REPO, "INCIDENT.json")],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env=cpu_env)
        blackbox_rc = br.returncode if br.returncode != 0 \
            else bsl.returncode
        gate = {"returncode": br.returncode,
                "slow_tests_returncode": bsl.returncode,
                "slow_tests_tail":
                    "\n".join(bsl.stdout.splitlines()[-1:]),
                "stderr_tail": "\n".join(br.stderr.splitlines()[-6:])}
        try:
            with open(os.path.join(_REPO, "INCIDENT.json")) as f:
                rep = json.load(f)
            gate["gate_ok"] = rep["gate_ok"]
            gate["checks"] = rep["checks"]
            gate["first_failure"] = rep["first_failure"]
        except (OSError, ValueError, KeyError):
            pass
        artifact["blackbox"] = gate
    except subprocess.TimeoutExpired:
        blackbox_rc = -1
        artifact["blackbox"] = {"returncode": -1, "note": "timed out"}

    # mxir stage (ISSUE 19): the StableHLO auditor's end-to-end
    # known-answer selftest — per-rule seeded/clean fixture pairs, the
    # PR 18 replicated-gather caught live, the static wire-bytes model
    # checked against the measured collective counter, and the
    # audit-off overhead bound — refreshing MXIR.json, the tracked
    # artifact perf_compare gates with STRICT lanes (a rule that stops
    # firing on its seeded fixture is never grandfathered).  Runs
    # BEFORE perf-compare so the artifact it diffs is fresh.
    mxir_rc = None
    try:
        ir = subprocess.run(
            [sys.executable, "tools/mxir.py", "--selftest",
             "--out", os.path.join(_REPO, "MXIR.json")],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env=cpu_env)
        mxir_rc = ir.returncode
        gate = {"returncode": ir.returncode,
                "tail": "\n".join(ir.stdout.splitlines()[-6:]),
                "stderr_tail": "\n".join(ir.stderr.splitlines()[-6:])}
        try:
            with open(os.path.join(_REPO, "MXIR.json")) as f:
                rep = json.load(f)
            gate["gate_ok"] = rep["gate_ok"]
            gate["stages"] = {k: v.get("ok")
                              for k, v in rep["stages"].items()}
        except (OSError, ValueError, KeyError):
            pass
        artifact["mxir"] = gate
    except subprocess.TimeoutExpired:
        mxir_rc = -1
        artifact["mxir"] = {"returncode": -1, "note": "timed out"}

    # mxrank stage (ISSUE 20): cross-rank collective-schedule
    # verification, both halves — the repo must lint CLEAN under
    # MX019/MX020 strict (no baseline: a rank-divergent schedule is
    # never grandfathered), the fixture/ledger/reclassification units
    # must hold, and the slow 2-process chaos e2e must classify a live
    # divergence as ScheduleDivergence with ZERO restarts.  Refreshes
    # MXRANK.json, the tracked artifact perf_compare gates with
    # STRICT lanes.  Runs BEFORE perf-compare so the diff is fresh.
    mxrank_rc = None
    try:
        lint = subprocess.run(
            [sys.executable, "tools/mxlint.py", "mxnet_tpu",
             "--enable", "MX019,MX020"],
            capture_output=True, text=True, timeout=600, cwd=_REPO,
            env=cpu_env)
        unit = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_mxrank.py",
             "-q", "-m", "not slow", "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=600, cwd=_REPO,
            env=cpu_env)
        e2e = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_mxrank.py",
             "-q", "-m", "slow", "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=900, cwd=_REPO,
            env=cpu_env)
        checks = {"lint_clean": lint.returncode == 0,
                  "unit": unit.returncode == 0,
                  "e2e_divergence": e2e.returncode == 0}
        rep = {"gate_ok": all(checks.values()), "checks": checks,
               "returncodes": {"lint": lint.returncode,
                               "unit": unit.returncode,
                               "e2e": e2e.returncode}}
        with open(os.path.join(_REPO, "MXRANK.json"), "w") as f:
            json.dump(rep, f, indent=1)
        mxrank_rc = 0 if rep["gate_ok"] else 1
        artifact["mxrank"] = {
            "returncode": mxrank_rc, "gate_ok": rep["gate_ok"],
            "checks": checks,
            "lint_tail": "\n".join(lint.stdout.splitlines()[-2:]),
            "unit_tail": "\n".join(unit.stdout.splitlines()[-2:]),
            "e2e_tail": "\n".join(e2e.stdout.splitlines()[-2:])}
    except subprocess.TimeoutExpired:
        mxrank_rc = -1
        artifact["mxrank"] = {"returncode": -1, "note": "timed out"}

    # perf-compare gate (ISSUE 10): the bench artifacts this nightly
    # just refreshed (FUSED/SCALING/COMPILE_CACHE/HEALTH; SERVING when
    # its strict lane rewrote it) vs the committed versions — >10%
    # throughput drop, MFU/data-wait attribution regression, or a NEW
    # trace-integrity/health failure fails the run.
    # Runs LAST so every refresh above has landed in the work tree.
    perf_rc = None
    try:
        pcr = subprocess.run(
            [sys.executable, "tools/perf_compare.py", "--ref", "HEAD",
             "--out", os.path.join(_REPO, "PERF_COMPARE.json")],
            capture_output=True, text=True, timeout=120, cwd=_REPO,
            env=cpu_env)
        perf_rc = pcr.returncode
        artifact["perf_compare"] = {
            "returncode": pcr.returncode,
            "tail": "\n".join(pcr.stdout.splitlines()[-1:]),
            "stderr_tail": "\n".join(pcr.stderr.splitlines()[-8:])}
    except subprocess.TimeoutExpired:
        perf_rc = -1
        artifact["perf_compare"] = {"returncode": -1,
                                    "note": "timed out"}

    artifact["duration_s"] = round(time.time() - t0, 1)  # incl. gate
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(out.splitlines()[-1] if out.splitlines() else "")
    print(f"wrote {args.out}")
    return 0 if p.returncode == 0 and opperf_rc in (None, 0) \
        and fused_rc in (None, 0) and trace_rc in (None, 0) \
        and mxlint_rc in (None, 0) and san_rc in (None, 0) \
        and resil_rc in (None, 0) and elastic_rc in (None, 0) \
        and cc_rc in (None, 0) \
        and spmd_rc in (None, 0) and heavy_rc in (None, 0) \
        and mxprof_rc in (None, 0) and health_rc in (None, 0) \
        and triage_rc in (None, 0) and goodput_rc in (None, 0) \
        and autotune_rc in (None, 0) and blackbox_rc in (None, 0) \
        and mxir_rc in (None, 0) and mxrank_rc in (None, 0) \
        and perf_rc in (None, 0) else 1


if __name__ == "__main__":
    sys.exit(main())
