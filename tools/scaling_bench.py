"""Data-parallel scaling-efficiency harness (BASELINE scaling target:
>=90% efficiency at 256 v5e chips).

Three step paths share one harness (``--path``):

  * ``replica`` — the per-replica pipeline: eager fwd/bwd (autograd),
    ``KVStore.pushpull_fused`` bucketed gradient sync (DCN/gloo across
    processes), per-replica ``FusedUpdater`` dispatches.
  * ``spmd``    — the unified GSPMD step (ISSUE 9): same eager fwd/bwd,
    but the gradient reduce + optimizer apply run as ONE jit program
    over the cross-process mesh with ZeRO-sharded optimizer states
    (``Trainer(spmd=True)``, optimizer/spmd.py).
  * ``gspmd``   — the whole step (fwd+bwd+reduce+update) as one sharded
    program (``parallel.SPMDTrainer``).

Weak-scaling throughput: the per-device batch is fixed, so perfect
scaling doubles global throughput when the process count doubles.

**Loss parity** (ISSUE 9 satellite): the old sweep let the global batch
grow with the process count, so the reported losses (one overfit run
per count on DIFFERENT data) were incomparable — SCALING.json read
0.035 → 1.26 → 2.40 and looked like a gradient-averaging bug.  The
parity stage pins the GLOBAL batch and seed across process counts
(same data, disjointly sharded by rank, gradients averaged over the
global batch via ``step(global_batch)``) and asserts the loss curves
agree; it runs on a BatchNorm-free MLP by default so the only
tolerated noise is collective summation order.  A real averaging or
sharding bug fails the gate.

The sweep is a CPU substrate: every worker it spawns is pinned to
JAX_PLATFORMS=cpu with one virtual device (N processes cannot share one
chip), over gloo on localhost — that validates the harness, the
multi-process program, and the efficiency accounting, NOT real ICI/DCN
bandwidth, and its MFU is null (a CPU has no entry in the peak table).  The identical command on a v5e pod (one process per host,
libtpu discovers local chips, DCN carries cross-host collectives):

    # on every host i of an N-host v5e pod:
    DMLC_PS_ROOT_URI=<host0-ip> DMLC_PS_ROOT_PORT=9876 \
    DMLC_NUM_WORKER=<N> DMLC_WORKER_ID=<i> \
    python tools/scaling_bench.py --_worker --path spmd \
        --model resnet50 --batch-per-device 256 --image-size 224 \
        --dtype bfloat16 --steps 50

(tools/launch.py -n N --launcher ssh automates exactly this env
contract; see docs/distributed.md.)  Dev-box sweep:

    python tools/scaling_bench.py --procs 1,2 --path spmd --phases
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
_TOOLS = os.path.dirname(os.path.abspath(__file__))
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

_PHASE_NAMES = ("forward", "backward", "grad-allreduce",
                "optimizer-update", "fused-update", "spmd-step",
                "reduce-scatter", "shard-update", "all-gather")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# models
# ---------------------------------------------------------------------------

def _build_model(args, rng, bs_global):
    """-> (net, data tuple, label, loss, opt, opt_args)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss

    if args.model == "mlp":
        # BatchNorm-free: the parity gate's oracle (local BN statistics
        # legitimately differ per process count; dense math does not)
        from mxnet_tpu.gluon import nn

        net = nn.HybridSequential()
        net.add(nn.Dense(64, activation="relu"),
                nn.Dense(32, activation="relu"), nn.Dense(8))
        net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
        with mx.autograd.pause():
            net(mx.nd.zeros((1, 16)))
        data = rng.rand(bs_global, 16).astype(args.dtype)
        label = rng.randint(0, 8, (bs_global,)).astype(np.int32)
        return (net, (data,), label, gloss.SoftmaxCrossEntropyLoss(),
                "sgd", {"learning_rate": 0.05, "momentum": 0.9})
    if args.model.startswith("resnet"):
        from mxnet_tpu.gluon.model_zoo import vision

        net = getattr(vision, args.model + "_v1")(classes=1000,
                                                  layout="NHWC")
        net.initialize(mx.initializer.Xavier(magnitude=2.0), ctx=mx.cpu())
        with mx.autograd.pause():
            net(mx.nd.zeros((1, 32, 32, 3)))
        if args.dtype != "float32":
            net.cast(args.dtype)
        s = args.image_size
        data = rng.rand(bs_global, s, s, 3).astype(args.dtype)
        label = rng.randint(0, 1000, (bs_global,)).astype(np.int32)
        return (net, (data,), label, gloss.SoftmaxCrossEntropyLoss(),
                "sgd", {"learning_rate": 0.1, "momentum": 0.9})
    if args.model == "bert":
        from mxnet_tpu.gluon.model_zoo.bert import get_bert_model

        seq = args.seq_len
        small = args.image_size < 224  # dev-box shapes
        vocab = 1000 if small else 30522
        kw = (dict(num_layers=2, units=64, hidden_size=128, num_heads=4,
                   max_length=seq) if small else dict(max_length=512))
        net = get_bert_model("bert_12_768_12", vocab_size=vocab, **kw)
        net.initialize(mx.initializer.Normal(0.02), ctx=mx.cpu())
        with mx.autograd.pause():
            seq_o, pooled = net(mx.nd.zeros((1, seq)),
                                mx.nd.zeros((1, seq)), mx.nd.array([seq]))
            net.decode_mlm(seq_o)       # resolve the head params too —
            net.classify_nsp(pooled)    # the trainer shards ALL of them
        if args.dtype != "float32":
            net.cast(args.dtype)
        data = (rng.randint(5, vocab, (bs_global, seq)).astype(np.int32),
                np.zeros((bs_global, seq), np.int32),
                np.full((bs_global,), seq, np.float32))
        label = rng.randint(0, 2, (bs_global,)).astype(np.int32)

        class _NSPLoss:
            """CLS-token 2-way loss — enough to drive the full encoder."""

            def __call__(self, out, y):
                import jax as _jax
                import jax.numpy as jnp

                cls = out[:, 0, :2].astype(jnp.float32)
                lsm = _jax.nn.log_softmax(cls, -1)
                return -jnp.take_along_axis(
                    lsm, y[:, None].astype(jnp.int32), -1)[:, 0]

        return (net, data, label, _NSPLoss(), "adam",
                {"learning_rate": 1e-4})
    raise SystemExit(f"unknown model {args.model}")


def _phase_report(trace_path):
    """Per-phase wall seconds from this rank's trace dump — consumed
    through tools/trace_report.py's machine-readable report (the same
    `--json` document, integrity verdict included) instead of a
    parallel metrics-table parse — plus collective bytes, per-step
    MFU, and peak HBM per device from the mxprof flight recorder."""
    import trace_report as tr
    from mxnet_tpu.telemetry import mxprof

    rep = tr.report_json(tr.load_trace(trace_path))
    phases = {}
    for row in rep["phases"]:
        if row["cat"] == "training" and row["name"] in _PHASE_NAMES \
                and row["count"]:
            phases[row["name"]] = {
                "seconds": round(row["total_ms"] / 1e3, 4),
                "count": row["count"]}
    snap = mxprof.snapshot(live_hbm=True)
    recs = snap["records"]
    mfus = [r["mfu"] for r in recs]
    out = {
        "phase_seconds": phases,
        "trace_check_ok": rep["check"]["ok"],
        "collective_bytes": snap["summary"].get("collective_bytes", {}),
        # wire view (keys "op@axis:encoding"): what actually crossed
        # the interconnect — under MXNET_COMM_QUANT this diverges from
        # the model-sized logical bytes above, and the nightly's
        # <=0.30x gate reads THIS
        "collective_wire_bytes": snap["summary"].get(
            "collective_wire_bytes", {}),
        "mfu": {
            "per_step": mfus,
            "mean": snap["summary"].get("mfu_mean"),
            "peak_flops": snap["peak_flops"],
        },
        "hbm_peak_bytes": {dev: row["peak_bytes"]
                           for dev, row in snap["hbm"].items()},
        "verdicts": snap["summary"].get("verdicts", {}),
        # attribution lane for perf_compare: input-pipeline stall
        # seconds over the measured steps (lower is better; gates
        # independently of throughput)
        "data_wait_s": snap["summary"].get("data_wait_s_total", 0.0),
        # mxtriage regression-attribution lanes: compile counts (with
        # provenance reasons when any miss was diffed), the compiled
        # programs' identities, and the registered-knob surface — so a
        # failing nightly can name its suspect instead of a bare %
        "compiles": snap["summary"].get("compiles", 0),
        "hlo_fingerprints": sorted({
            row["hlo_fingerprint"]
            for per in snap.get("executable_costs", {}).values()
            for row in per.values() if row.get("hlo_fingerprint")}),
        "knobs": snap.get("knobs", {}),
        "knob_fingerprint": snap.get("knob_fingerprint"),
    }
    reasons = snap["summary"].get("compile_reasons")
    if reasons:
        out["compile_reasons"] = reasons
    state = snap.get("optimizer_state_bytes_per_device")
    if state:
        out["optimizer_state_bytes_per_device"] = state
    # goodput lane: the ledger rode the snapshot (mxgoodput was
    # enabled for the attribution steps) — rows carry the ratio and
    # the badput decomposition so mxtriage attribution can rank a
    # badput-category shift as a suspect
    good = snap.get("goodput")
    if isinstance(good, dict):
        out["goodput_ratio"] = good.get("goodput_ratio")
        out["badput_seconds"] = good.get("badput_s", {})
        # the comm-stall lane the overlap gate reads: EXPOSED
        # communication seconds (overlap hides comm inside the update
        # dispatch, so this drops when MXNET_COMM_OVERLAP earns it)
        out["comm_stall_s"] = round(float(
            good.get("badput_s", {}).get("comm_stall", 0.0)), 6)
    return out


# ---------------------------------------------------------------------------
# worker (one process of the job)
# ---------------------------------------------------------------------------

def worker(args):
    import numpy as np

    if args.path == "spmd":
        os.environ.setdefault("MXNET_SPMD", "1")
    else:
        # pin the baseline: an MXNET_SPMD=1 inherited from the
        # operator's shell must not turn the per-replica measurement
        # into a second SPMD run (the nightly gate compares the two)
        os.environ["MXNET_SPMD"] = "0"
    # pin the comm lane the same way: the quantized/overlapped rows and
    # the raw baseline must not bleed into each other via the shell
    os.environ["MXNET_COMM_QUANT"] = args.quant
    os.environ["MXNET_COMM_OVERLAP"] = "1" if args.overlap else "0"
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.parallel import dist

    dist.init()
    import jax

    n_dev = jax.device_count()
    n_proc = jax.process_count()
    rank = jax.process_index()
    n_local = jax.local_device_count()
    bs_global = args.global_batch or args.batch_per_device * n_dev
    if bs_global % n_dev:
        raise SystemExit(f"global batch {bs_global} not divisible by "
                         f"{n_dev} devices")

    # THE loss-parity fix (ISSUE 9 satellite): every rank must
    # initialize the SAME model.  The parameter init draws from the
    # framework RNG, which seeds nondeterministically per process —
    # unseeded, each rank trains a DIFFERENT model whose replicated
    # params only pretend to agree, and the sweep's losses drift with
    # the process count (SCALING.json 0.035 -> 1.26 -> 2.40).  Data
    # stays rank-identical too (the launcher contract: every process
    # generates the global batch, then shards it disjointly).
    mx.random.seed(args.seed)
    np.random.seed(args.seed)  # initializers draw from global numpy too
    rng = np.random.RandomState(args.seed)
    net, data, label, loss, opt, opt_args = _build_model(args, rng,
                                                         bs_global)

    if args.path == "gspmd":
        lval, dt, trace = _run_gspmd(args, mx, parallel, net, data,
                                     label, loss, opt, opt_args, n_dev,
                                     rank)
    else:
        lval, dt, trace = _run_trainer(args, mx, net, data, label,
                                       loss, opt, opt_args, bs_global,
                                       n_proc, rank, n_local)

    tp = bs_global * args.steps / dt
    # only rank 0 reports; the live-array HBM scan + trace parse in
    # _phase_report is pure waste on the other ranks
    phase_rep = _phase_report(trace) if trace and rank == 0 else None
    if rank == 0:
        # the quantized lane is its OWN path label ("spmd-int8"): its
        # rows sit beside the raw rows in SCALING.json and diff/gate
        # against them instead of silently replacing them
        path_label = args.path if args.quant == "none" \
            else f"{args.path}-{args.quant}"
        row = {
            "model": args.model, "path": path_label,
            "processes": n_proc, "devices": n_dev,
            "batch_per_device": bs_global // n_dev,
            "global_batch": bs_global,
            "global_throughput": round(tp, 2),
            "per_device_throughput": round(tp / n_dev, 2),
            "unit": "samples/s", "loss": round(lval, 4),
        }
        if phase_rep:
            row.update(phase_rep)
        print(json.dumps(row), flush=True)
    return 0


def _attribution_steps(args, one_step, rank):
    """--phases: run a couple of EXTRA traced+profiled steps AFTER the
    timed window — the phased SPMD variant and the span bookkeeping
    must never distort the throughput/efficiency numbers the sweep
    gates on (tracing serializes the step into per-phase dispatches).
    Every rank dumps its own trace (for the parent's multi-rank merge)
    and keeps the mxprof flight recorder attached for the MFU/HBM
    numbers the row reports.  Returns this rank's trace path."""
    if not args.phases:
        return None
    import tempfile

    from mxnet_tpu import profiler, telemetry
    from mxnet_tpu.telemetry import mxgoodput, mxprof

    telemetry.enable()  # span tracing + metrics + the mxprof recorder
    mxprof.clear()      # attribute ONLY the steps below
    mxgoodput.enable(fresh=True)  # goodput lane over the same window
    profiler.start()
    try:
        for _ in range(2):
            one_step()
    finally:
        profiler.stop()
        telemetry.disable()
    if args.trace_dir:
        path = os.path.join(args.trace_dir, f"trace_rank{rank}.json")
    else:
        fd, path = tempfile.mkstemp(prefix="mx_scaling_trace_",
                                    suffix=".json")
        os.close(fd)
    profiler.dump(finished=True, filename=path)
    return path


def _run_gspmd(args, mx, parallel, net, data, label, loss, opt,
               opt_args, n_dev, rank):
    import time as _t

    mesh = parallel.make_mesh(dp=n_dev)
    with mesh:
        trainer = parallel.SPMDTrainer(net, loss, opt, dict(opt_args))
        placed = [trainer._place(a, None) for a in data + (label,)]
        # >=1 unmeasured call: keeps compilation out of the timed window
        # and binds `lv` even for --warmup 0
        for _ in range(max(args.warmup, 1)):
            lv = trainer.step(*placed)
        lv.asnumpy()
        t0 = _t.perf_counter()
        for _ in range(args.steps):
            lv = trainer.step(*placed)
        lval = float(lv.asnumpy())
        dt = _t.perf_counter() - t0
        trace = _attribution_steps(
            args, lambda: trainer.step(*placed).asnumpy(), rank)
    return lval, dt, trace


def _run_trainer(args, mx, net, data, label, loss_fn, opt, opt_args,
                 bs_global, n_proc, rank, n_local):
    """The gluon Trainer paths (per-replica and unified SPMD): eager
    fwd/bwd on this process's disjoint shard of the global batch, then
    Trainer.step.  The loss reported is the GLOBAL batch mean (local
    sums allreduced), so it is comparable across process counts."""
    import time as _t

    import numpy as np
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.trainer import Trainer
    from mxnet_tpu.parallel import dist

    # disjoint shard: rank r owns rows [r*per_proc, (r+1)*per_proc)
    per_proc = bs_global // n_proc
    sl = slice(rank * per_proc, (rank + 1) * per_proc)
    local = [mx.nd.array(a[sl]) for a in data] + [mx.nd.array(label[sl])]
    *xs, y = local

    kv = "dist_sync" if n_proc > 1 else "device"
    trainer = Trainer(net.collect_params(), opt, dict(opt_args),
                      kvstore=kv, update_on_kvstore=False,
                      spmd=(args.path == "spmd"))

    def one_step():
        with autograd.record():
            out = net(*xs)
            outs = out if isinstance(out, (list, tuple)) else (out,)
            l = loss_fn(outs[0], y)
        l.backward()
        # sum-loss backward + step(global) = mean over the GLOBAL batch
        trainer.step(bs_global)
        return l

    for _ in range(max(args.warmup, 1)):
        l = one_step()
    l.asnumpy()
    t0 = _t.perf_counter()
    for _ in range(args.steps):
        l = one_step()
    local_sum = float(l.asnumpy().sum())
    dt = _t.perf_counter() - t0
    gsum = float(dist.allgather_np(np.asarray(local_sum)).sum())
    trace = _attribution_steps(args, lambda: one_step().asnumpy(), rank)
    return gsum / bs_global, dt, trace


# ---------------------------------------------------------------------------
# parent: localhost sweep over process counts
# ---------------------------------------------------------------------------

def _spawn_sweep(args, n):
    import shutil
    import tempfile

    port = str(_free_port())
    trace_dir = tempfile.mkdtemp(prefix="mx_scaling_traces_") \
        if args.phases else None
    procs = []
    for i in range(n):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env.update({"DMLC_ROLE": "worker", "DMLC_PS_ROOT_URI": "127.0.0.1",
                    "DMLC_PS_ROOT_PORT": port, "DMLC_NUM_WORKER": str(n),
                    "DMLC_WORKER_ID": str(i)})
        cmd = [sys.executable, os.path.abspath(__file__), "--_worker",
               "--model", args.model, "--path", args.path,
               "--steps", str(args.steps),
               "--warmup", str(args.warmup),
               "--batch-per-device", str(args.batch_per_device),
               "--image-size", str(args.image_size),
               "--seq-len", str(args.seq_len), "--dtype", args.dtype,
               "--seed", str(args.seed),
               "--global-batch", str(args.global_batch),
               "--quant", args.quant]
        if args.overlap:
            cmd.append("--overlap")
        if args.phases:
            cmd.append("--phases")
        if trace_dir:
            cmd += ["--trace-dir", trace_dir]
        procs.append(subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT, text=True))
    line = None
    try:
        for p in procs:
            out, _ = p.communicate(timeout=args.proc_timeout)
            if p.returncode != 0:
                tail = "\n".join(out.splitlines()[-12:])
                raise RuntimeError(f"worker rc={p.returncode}:\n{tail}")
            for ln in out.splitlines():
                if ln.startswith("{"):
                    line = ln
    finally:
        # a dead rank leaves the siblings blocked in a collective; never
        # leak them (they'd also hold the coordinator port)
        for p in procs:
            if p.poll() is None:
                p.kill()
    row = json.loads(line)
    if trace_dir:
        try:
            row.update(_merge_rank_traces(args, trace_dir, n))
        finally:
            if args.keep_traces:
                print(f"rank traces kept in {trace_dir}",
                      file=sys.stderr)
            else:
                shutil.rmtree(trace_dir, ignore_errors=True)
    return row


def _merge_rank_traces(args, trace_dir, n):
    """Clock-align + merge every rank's attribution trace and run the
    integrity gate on the result (trace_report --merge --check
    semantics, via the shared merge_loaded pipeline).  The merged
    trace lands next to --out for multi-rank runs so a regression can
    be inspected in Perfetto."""
    import glob

    import trace_report as tr

    paths = sorted(glob.glob(os.path.join(trace_dir, "trace_rank*.json")))
    if not paths:
        return {}
    loaded = [tr.load_trace(p) for p in paths]
    dst = os.path.splitext(args.out)[0] + f"_trace_{n}proc.json" \
        if len(loaded) > 1 and args.out else None
    merged, info, errs = tr.merge_loaded(loaded, out=dst)
    out = {"merged_trace": {
        "ranks": len(loaded), "events": len(merged),
        "check_ok": not errs,
        "violations": errs[:5],
        "offsets_us": info["offsets_us"],
        "skew_top": info["skew"][:5],
    }}
    if dst:
        out["merged_trace"]["path"] = os.path.basename(dst)
    return out


def _parity_stage(args, counts):
    """Same seed + same GLOBAL batch across process counts => the loss
    curves must agree (the gradients are averaged over the same data,
    only the sharding differs).  Returns the report dict; 'ok' is the
    gate."""
    gb = args.batch_per_device * max(counts)
    rows = []
    pa = argparse.Namespace(**vars(args))
    pa.model = args.parity_model
    pa.global_batch = gb
    for n in counts:
        rows.append(_spawn_sweep(pa, n))
    losses = [r["loss"] for r in rows]
    spread = max(losses) - min(losses)
    ref = max(abs(losses[0]), 1e-6)
    ok = spread / ref <= args.parity_tol
    return {"model": pa.model, "global_batch": gb,
            "steps": args.steps, "losses": losses,
            "rel_spread": round(spread / ref, 6),
            "tol": args.parity_tol, "ok": ok}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18",
                    choices=["mlp", "resnet18", "resnet50", "bert"])
    ap.add_argument("--path", default="replica",
                    choices=["replica", "spmd", "gspmd"])
    ap.add_argument("--spmd", action="store_true",
                    help="shorthand for --path spmd")
    ap.add_argument("--quant", default="none",
                    choices=["none", "int8", "fp8"],
                    help="collective wire encoding for the run "
                         "(MXNET_COMM_QUANT); the row's path label "
                         "becomes e.g. 'spmd-int8'")
    ap.add_argument("--overlap", action="store_true",
                    help="launch bucket collectives in gradient-ready "
                         "order (MXNET_COMM_OVERLAP=1)")
    ap.add_argument("--procs", default="1,2,4",
                    help="comma-separated process counts for the sweep")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--batch-per-device", type=int, default=8)
    ap.add_argument("--global-batch", type=int, default=0,
                    help="pin the GLOBAL batch (loss parity across "
                         "process counts); 0 = batch-per-device * n "
                         "(weak scaling)")
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--seed", type=int, default=0,
                    help="framework + data RNG seed (every rank MUST "
                         "agree — see the parity note in worker())")
    ap.add_argument("--phases", action="store_true",
                    help="report per-phase step-time attribution + "
                         "collective bytes, collected from 2 extra "
                         "traced steps AFTER the timed window (the "
                         "phased SPMD variant serializes dispatches; "
                         "it must not distort the gated efficiency)")
    ap.add_argument("--no-parity", action="store_true",
                    help="skip the fixed-global-batch loss-parity gate")
    ap.add_argument("--parity-model", default="mlp",
                    help="model for the parity stage (default: the "
                         "BatchNorm-free mlp — BN batch statistics "
                         "legitimately vary with the local batch)")
    ap.add_argument("--parity-tol", type=float, default=1e-3,
                    help="max relative spread of final losses across "
                         "process counts")
    ap.add_argument("--proc-timeout", type=float, default=900.0)
    ap.add_argument("--out", default=os.path.join(_REPO, "SCALING.json"))
    ap.add_argument("--keep-traces", action="store_true",
                    help="with --phases: keep each run's per-rank "
                         "trace dir instead of deleting it after the "
                         "merge")
    ap.add_argument("--trace-dir", default="", help=argparse.SUPPRESS)
    ap.add_argument("--_worker", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.spmd:
        args.path = "spmd"

    if args._worker:
        return worker(args)

    results = []
    counts = sorted({int(x) for x in args.procs.split(",")})
    base = base_n = None
    for n in counts:
        res = _spawn_sweep(args, n)
        if base is None:  # smallest count is the efficiency reference
            base, base_n = res["per_device_throughput"], n
        res[f"efficiency_vs_{base_n}proc"] = round(
            res["per_device_throughput"] / base, 4)
        results.append(res)
        print(json.dumps(res))

    report = {"when": time.strftime("%Y-%m-%d %H:%M:%S"),
              "backend": "cpu+gloo localhost (dev box)",
              "path": args.path,
              "quant": args.quant, "overlap": bool(args.overlap),
              "note": "validates harness+program, not ICI/DCN "
                      "bandwidth; see docstring for the pod command",
              "sweep": results}
    rc = 0
    if not args.no_parity and len(counts) > 1:
        parity = _parity_stage(args, counts)
        report["parity"] = parity
        print(json.dumps({"parity": parity}))
        if not parity["ok"]:
            print("PARITY GATE FAILED: loss curves diverge across "
                  "process counts", file=sys.stderr)
            rc = 1
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
