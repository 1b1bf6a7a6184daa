#!/usr/bin/env python
"""Elastic job supervisor (ISSUE 15): launch N workers, detect a dead
or hung rank, recover the job.

Thin CLI over :class:`mxnet_tpu.resilience.elastic.Supervisor` — the
detection/coordination/commit-marker logic lives in the framework so
real launchers can embed it; this tool adds argv plumbing, a built-in
demo training worker (the chaos e2e fixture), and a JSON report.

    # supervise your own worker command (rank env contract exported):
    python tools/elastic_run.py --workers 4 --dir /ckpt/job1 \
        --mode shrink -- python train.py --my-args

    # the built-in demo worker (deterministic MLP, dist_sync kvstore,
    # per-rank AutoCheckpoint, heartbeats) with a chaos kill of rank 1
    # at its 4th step, recovered in replace mode:
    JAX_PLATFORMS=cpu python tools/elastic_run.py --workers 2 --demo \
        --cpu --steps 8 --chaos "elastic.worker@4:die:rank=1"

Each worker sees ``MXNET_ELASTIC=1``, ``MXNET_ELASTIC_DIR/RANK/WORLD``
plus the dmlc launcher contract (fresh coordinator port per
generation) and a collective watchdog (``MXNET_KVSTORE_TIMEOUT``).
Failure recovery: wind down survivors (SIGTERM -> preemption seam ->
sync checkpoint -> reserved rc), elect the job-level commit marker
(one step dir every restarted rank resumes from — steps can never mix
across ranks), restart in **replace** (same world) or **shrink**
(world minus the failed ranks) mode, bounded by the restart budget.
The report records per-epoch MTTR (detection -> first post-resume
step, watched via the heartbeat step stamps).

The supervisor imports the framework but never initialises a jax
backend, so it holds no chip.  Several workers on one machine cannot
share its chips: `--cpu` pins each worker to JAX_PLATFORMS=cpu with one
virtual device (the only mode tests and the nightly use); without it,
give each worker its own host.

Exit: 0 when the job completed, 1 when it died (budget exhausted).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


# ---------------------------------------------------------------------------
# built-in demo worker: the smallest real multi-process training job
# with the full elastic contract (the chaos e2e + bench fixture)
# ---------------------------------------------------------------------------

def demo_worker(args) -> int:
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd, resilience
    from mxnet_tpu.gluon import Trainer, nn
    from mxnet_tpu.parallel import dist
    from mxnet_tpu.resilience import elastic

    dist.init()
    edir = elastic.shared_dir()
    rank, world = elastic.rank(), elastic.world()
    gb = args.global_batch

    # every rank must build the SAME model and data (the scaling_bench
    # parity lesson): seed the framework + numpy before init, generate
    # the GLOBAL batch everywhere, shard it disjointly by rank
    np.random.seed(args.seed)
    mx.random.seed(args.seed)
    rng = np.random.RandomState(args.seed)
    batches = [(rng.rand(gb, 16).astype("f4"),
                rng.rand(gb, 4).astype("f4"))
               for _ in range(args.steps)]
    net = nn.Dense(4, in_units=16, prefix="elastic_")
    net.initialize(ctx=mx.cpu())

    kv = "dist_sync" if world > 1 else "device"
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.05, "momentum": 0.9},
                 kvstore=kv, update_on_kvstore=False)
    pos = {"next_batch": 0}
    ck = resilience.AutoCheckpoint(
        os.path.join(edir, f"rank{rank}"), tr,
        every_n_steps=args.ckpt_every, async_save=False,
        state_provider=lambda: dict(pos))
    elastic.install_winddown()

    start = 0
    cpath = elastic.committed_resume_path(edir)
    if cpath is not None:
        # the commit marker carries the mxblackbox incident id of the
        # failure epoch this resume recovers from — it stamps the
        # goodput rank_failure_recovery window
        commit = elastic.read_commit(edir) or {}
        meta = ck.resume(path=cpath, incident=commit.get("incident"))
        # the demo maps one batch to one step, so the committed step
        # counter IS the resume index (the commit marker guarantees
        # every rank picked the same one)
        start = int(meta["step"])
    wc = elastic.WorkerContext()
    wc.heartbeat.beat(step=start)

    per = gb // world
    sl = slice(rank * per, (rank + 1) * per) if world > 1 \
        else slice(None)
    with elastic.guard(auto_ckpt=ck):
        for i in range(start, args.steps):
            xb, yb = batches[i]
            pos["next_batch"] = i + 1
            with autograd.record():
                loss = ((net(nd.array(xb[sl], ctx=mx.cpu()))
                         - nd.array(yb[sl], ctx=mx.cpu())) ** 2).sum()
            loss.backward()
            tr.step(gb)  # sum-loss backward + global bs = global mean
            wc.on_step(i + 1)
        # the reported loss is a POST-final-update forward pass on the
        # last batch — the one definition every path shares: a normal
        # run, a recovered run, and a resume that landed past the end
        # (commit step == steps) all report the same quantity, so the
        # bench's twin-parity comparison is apples to apples
        xb, yb = batches[-1]
        with autograd.pause():
            final = ((net(nd.array(xb[sl], ctx=mx.cpu()))
                      - nd.array(yb[sl], ctx=mx.cpu())) ** 2).sum()
        local = float(final.asnumpy().sum())
        gsum = float(dist.allgather_np(np.asarray(local)).sum())
        if rank == 0:
            result = {"loss": round(gsum / gb, 8), "world": world,
                      "steps": args.steps, "t_unix": time.time()}
            tmp = os.path.join(edir, ".tmp-result.json")
            with open(tmp, "w") as f:
                json.dump(result, f)
            os.replace(tmp, os.path.join(edir, "result.json"))
    return 0


# ---------------------------------------------------------------------------
# supervisor CLI
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="supervise an N-rank training job with coordinated "
                    "rank-failure recovery (shrink/replace restarts)")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--mode", choices=["replace", "shrink"],
                    default="replace")
    ap.add_argument("--dir", default=None,
                    help="shared elastic dir (default: a fresh tempdir)")
    ap.add_argument("--max-restarts", type=int, default=None)
    ap.add_argument("--hb-timeout", type=float, default=None,
                    help="heartbeat staleness -> hung (default: "
                         "MXNET_ELASTIC_HEARTBEAT_TIMEOUT_S)")
    ap.add_argument("--collective-timeout", type=float, default=None,
                    help="MXNET_KVSTORE_TIMEOUT exported to workers "
                         "(default: the heartbeat timeout)")
    ap.add_argument("--grace", type=float, default=None,
                    help="wind-down grace before SIGKILL")
    ap.add_argument("--startup-timeout", type=float, default=None,
                    help="a rank with NO heartbeat stamp past this "
                         "window is classified hung (default: "
                         "max(60, 4x hb timeout); 0 disables for "
                         "worker commands that never beat)")
    ap.add_argument("--poll", type=float, default=0.25)
    ap.add_argument("--chaos", default=None,
                    help="MXNET_CHAOS_SPEC exported to GENERATION 0 "
                         "only (e.g. 'elastic.worker@4:die:rank=1')")
    ap.add_argument("--cpu", action="store_true",
                    help="pin workers to the single-device CPU+gloo "
                         "backend (dev box / CI)")
    ap.add_argument("--demo", action="store_true",
                    help="supervise the built-in demo training worker")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="also write the report JSON here")
    ap.add_argument("--_demo-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("worker_cmd", nargs="*",
                    help="worker command (after --); omit with --demo")
    args = ap.parse_args(argv)

    if args._demo_worker:
        return demo_worker(args)

    from mxnet_tpu.resilience.elastic import Supervisor

    if args.demo:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--_demo-worker", "--steps", str(args.steps),
               "--ckpt-every", str(args.ckpt_every),
               "--global-batch", str(args.global_batch),
               "--seed", str(args.seed)]
    elif args.worker_cmd:
        cmd = args.worker_cmd
    else:
        print("error: give a worker command or --demo", file=sys.stderr)
        return 2

    directory = args.dir or tempfile.mkdtemp(prefix="mx-elastic-")
    base_env = dict(os.environ)
    if args.cpu:
        base_env["JAX_PLATFORMS"] = "cpu"
        base_env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    if args.chaos:
        base_env["MXNET_CHAOS"] = "1"
        base_env["MXNET_CHAOS_SPEC"] = args.chaos

    # convert an outer SIGTERM (a CI timeout terminating this
    # supervisor) into SystemExit so Supervisor.run's teardown kills
    # the live worker generation instead of orphaning it
    import signal as _signal

    _signal.signal(_signal.SIGTERM, lambda s, f: sys.exit(143))

    sup = Supervisor(cmd, world=args.workers, directory=directory,
                     mode=args.mode, max_restarts=args.max_restarts,
                     hb_timeout_s=args.hb_timeout,
                     grace_s=args.grace,
                     collective_timeout_s=args.collective_timeout,
                     poll_s=args.poll,
                     startup_timeout_s=args.startup_timeout,
                     base_env=base_env)
    t0 = time.time()
    report = sup.run()
    report["duration_s"] = round(time.time() - t0, 3)
    report["dir"] = directory
    try:
        with open(os.path.join(directory, "result.json")) as f:
            report["result"] = json.load(f)
    except (OSError, ValueError):
        pass
    line = json.dumps(report)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
