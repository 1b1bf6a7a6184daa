#!/usr/bin/env python
"""Run the opt-in REAL-DEVICE suite (tests/test_tpu_device.py) on the
TPU and write a committed artifact with the results.

Counterpart of the reference's GPU test lane
(tests/python/gpu/test_operator_gpu.py in CI).  Usage:

    python tools/run_tpu_tests.py [--out TPU_TESTS.json]

Sets MXNET_TEST_PLATFORM=tpu so tests/conftest.py keeps the accelerator
visible, runs pytest on the on-device module in ONE child (this parent
stays off jax, so the child takes the chip alone), and writes
{passed, failed, skipped, duration_s, platform, device, cases} as JSON.
Through the chip tool: `--out chiprun_out/TPU_TESTS.json`.
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(_REPO, "TPU_TESTS.json"))
    ap.add_argument("--timeout", type=float, default=1500.0)
    args = ap.parse_args()

    env = dict(os.environ, MXNET_TEST_PLATFORM="tpu")
    t0 = time.time()
    try:
        p = subprocess.run(
            [sys.executable, "-m", "pytest",
             os.path.join(_REPO, "tests", "test_tpu_device.py"),
             "-v", "--tb=short", "-rN"],
            capture_output=True, text=True, timeout=args.timeout, env=env,
            cwd=_REPO)
        out = p.stdout
        rc = p.returncode
    except subprocess.TimeoutExpired as e:
        out = (e.stdout or b"").decode() if isinstance(e.stdout, bytes) \
            else (e.stdout or "")
        rc = -1
    dur = time.time() - t0

    cases = {}
    for ln in out.splitlines():
        m = re.match(r"tests/test_tpu_device\.py::(\S+)\s+(PASSED|FAILED|"
                     r"SKIPPED|ERROR)", ln)
        if m:
            cases[m.group(1)] = m.group(2)
    tally = re.search(r"(\d+) passed", out)
    failed = re.search(r"(\d+) failed", out)
    skipped = re.search(r"(\d+) skipped", out)
    errors = re.search(r"(\d+) errors?", out)

    # the pytest process holds the chip and names it in its header
    # (tests/conftest.py:pytest_report_header)
    m = re.search(r"mxnet_tpu device: platform=(\S+) kind='([^']*)' "
                  r"count=(\d+)", out)
    platform, device, count = (m.group(1), m.group(2), int(m.group(3))) \
        if m else ("unknown", "unknown", 0)

    artifact = {
        "suite": "tests/test_tpu_device.py",
        "platform": platform,
        "device": device,
        "n_devices": count,
        "passed": int(tally.group(1)) if tally else 0,
        "failed": int(failed.group(1)) if failed else 0,
        "skipped": int(skipped.group(1)) if skipped else 0,
        "errors": int(errors.group(1)) if errors else 0,
        "duration_s": round(dur, 1),
        "returncode": rc,
        "cases": cases,
    }
    # keep the failures' tracebacks in the artifact: the machine that ran
    # them is gone when anyone wants to debug them
    m = re.search(r"=+ FAILURES =+\n(.*?)\n=+ (?:warnings summary|short "
                  r"test summary|\d+ (?:failed|passed))", out, re.S)
    if m:
        artifact["failures"] = m.group(1)[-8000:]
    if not cases and rc != 0:
        # a broken run (collection/import error) must never read green
        artifact["status"] = "BROKEN_RUN"
        artifact["output_tail"] = out[-1500:]
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps({k: v for k, v in artifact.items() if k != "cases"}))
    return 0 if rc == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
