"""One cell, one run, one new process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model through the program's normal training path
(model-zoo block on cpu() from the seed, cast, parallel.make_mesh,
parallel.SPMDTrainer), places the resident batch, holds the model to its
plain reference, warms the cell's one shape, measures, and prints as the
last line of stdout one JSON object with exactly the keys `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` when traced).
Everything else worth reading is on earlier `[info] {...}` lines.

It measures only on exactly the cell's number of TPU chips: anything else
is exit 2 and no result line.  `--rehearse` is the builder's dry run (tiny
shapes from the configuration's `rehearsal` block on the CPU backend,
every metric null, platform printed as cpu); the driver never passes it.
See benchmark/README.md.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, REPO)            # the program under test: mxnet_tpu

from harness import lookup, result, window  # noqa: E402

WARMUP_STEPS = 3
TRACED_STEPS = 8
TRACE_LEAD_IN = window.RUN_AHEAD    # steps traced before the counted ones
TRACE_DIR = os.path.join(REPO, ".bench_trace")


def info(**fields) -> None:
    print("[info] " + json.dumps(fields, default=str), flush=True)


class Phases:
    """Seconds of set-up by phase, for the info line: set-up is most of
    what a check costs, so where it goes is worth a line."""

    def __init__(self):
        self.seconds, self._last = {}, T_PROCESS

    def done(self, name) -> None:
        now = time.perf_counter()
        self.seconds[name] = round(now - self._last, 3)
        self._last = now


def rehearsal(cell) -> None:
    """Shrink the cell to the configuration's rehearsal sizes, on CPU."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if cell.chips > 1 and "host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={cell.chips}")
    small = cell.config["rehearsal"]
    cell.config.update(small["model"])
    cell.traffic.update(small["traffic"])
    cell.traffic["batch"] = cell.traffic.pop("batch_per_chip") * cell.chips


def placer(mesh):
    """put(x): a host array, or put(draw, *args): what the function
    `draw(*args)` makes on the device, onto the mesh with its first
    dimension split over dp.  What depends on the seed goes in `args`, so
    that the drawing program is the same for every seed and the compile
    cache finds it."""
    import jax
    import numpy as np

    from mxnet_tpu import parallel

    def put(x, *args):
        if callable(x):
            ndim = len(jax.eval_shape(x, *args).shape)
            return jax.jit(x, out_shardings=parallel.shard_batch(
                mesh, extra_dims=ndim - 1))(*args)
        x = np.asarray(x)
        return jax.device_put(x, parallel.shard_batch(
            mesh, extra_dims=x.ndim - 1))
    return put


def reference_params(trainer):
    """Float32 copies of the placed parameters (the step donates the
    originals), named as the zoo names them less the block's prefix.
    One program for all of them, so one entry in the compile cache."""
    import jax
    import jax.numpy as jnp

    prefix = os.path.commonprefix(list(trainer.params))
    prefix = prefix[:prefix.rfind("_") + 1]
    return jax.jit(lambda params: {
        n[len(prefix):]: v.astype(jnp.float32)
        for n, v in params.items()})(trainer.params)


def rel_l2(got, want) -> float:
    import numpy as np

    return float(np.linalg.norm((got - want).ravel())
                 / max(np.linalg.norm(want.ravel()), 1e-30))


def rates(run, traffic) -> dict:
    """Samples/s, and tokens/s where the traffic has a sequence length,
    for the info line."""
    out = {"samples_per_s": lookup.metric_reader("e2e_metrics",
                                                 "throughput")(run)}
    if "seq_len" in traffic:
        out["tokens_per_s"] = out["samples_per_s"] * traffic["seq_len"]
    return out


def built(stats) -> int:
    return stats["count"] + stats["cache_loads"]


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args()


def on_the_chips(arrays, devices) -> bool:
    on = set(devices)
    return all({s.device for s in a.addressable_shards} == on
               for a in arrays)


def set_up(cell, seed, devices, phases):
    """Build, place, hold to the reference, warm the one shape.
    -> (trainer, batch, checks, facts for the info line)."""
    import jax
    import numpy as np

    config, traffic, model = cell.config, cell.traffic, cell.model
    checks = {}
    trainer = model.build(seed, config, traffic, cell.chips)
    phases.done("build")
    batch = model.batch(seed, config, traffic, placer(trainer.mesh))
    jax.block_until_ready(batch)
    phases.done("batch")
    checks["batch_split_over_the_chips"] = on_the_chips(batch, devices) \
        and all(s.data.shape[0] == traffic["batch"] // cell.chips
                for a in batch for s in a.addressable_shards)
    checks["parameters_on_the_chips"] = on_the_chips(
        trainer.params.values(), devices)

    tol = config["reference_check"]
    params = reference_params(trainer)
    sample = model.sample(seed, config, traffic)
    got = model.system_logits(trainer, sample, config)
    want = model.reference_logits(cell.reference, params, sample, config)
    errors = {k: rel_l2(got[k], want[k]) for k in want}
    checks["logits_agree_with_reference"] = all(
        np.isfinite(got[k]).all() and e <= tol["logits_rel_l2_tol"]
        for k, e in errors.items())
    ref_loss = model.reference_first_loss(cell.reference, params, batch,
                                          config)
    del params, got, want       # 4 bytes a parameter, off the chip again
    phases.done("reference_check")

    donated = next(iter(trainer.params.values()))
    t_first = time.perf_counter()
    first_loss = float(trainer.step(*batch).asnumpy())
    first_step_s = time.perf_counter() - t_first
    checks["parameters_donated"] = bool(donated.is_deleted())
    checks["first_loss_finite"] = bool(np.isfinite(first_loss))
    if ref_loss is not None:
        checks["first_loss_agrees_with_reference"] = (
            abs(first_loss - ref_loss) <= tol["first_loss_abs_tol"])
    for _ in range(WARMUP_STEPS - 1):
        loss = trainer.step(*batch)
    loss.asnumpy()
    phases.done("first_step_and_warmup")
    return trainer, batch, checks, {
        "first_step_s": first_step_s, "reference_rel_l2": errors,
        "first_loss": first_loss, "reference_first_loss": ref_loss}


def traced_window(step, cell_name):
    """2 lead-in + 8 counted steps under the profiler, reduced."""
    import jax

    from harness import trace_reduce

    trace_dir = os.path.join(TRACE_DIR, cell_name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        win = window.run(step, steps=TRACE_LEAD_IN + TRACED_STEPS,
                         span=jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    return win, trace_reduce.reduce(trace_reduce.newest_xplane(trace_dir),
                                    steps=TRACED_STEPS)


def memory_counters(trainer, devices):
    """-> (peak bytes on the fullest chip, facts for the info line).  The
    allocator's peak leaves out the step program's temporaries (PR 21:
    0.29 GB against 8.5 GB planned), so the peak on a chip is the larger
    of the allocator's own peak (the reference check's copies) and what
    is live in the steady loop plus the temporaries."""
    memory = [d.memory_stats() or {} for d in devices]
    (step_fn, _cost), = trainer._step_fns.values()
    program = step_fn.memory_analysis()
    temporaries = program.temp_size_in_bytes
    peak = max(max(m.get("peak_bytes_in_use", 0),
                   m.get("bytes_in_use", 0) + temporaries) for m in memory)
    return peak, {
        "program_bytes": {"arguments": program.argument_size_in_bytes,
                          "outputs": program.output_size_in_bytes,
                          "aliased": program.alias_size_in_bytes,
                          "temporaries": temporaries},
        "allocator_peak_bytes": [m.get("peak_bytes_in_use") for m in memory],
        "allocator_live_bytes": [m.get("bytes_in_use") for m in memory],
        "bytes_limit": memory[0].get("bytes_limit")}


def main() -> int:
    args = parse_args()
    phases = Phases()
    cell = lookup.cell(args.workload)
    if args.rehearse:
        rehearsal(cell)

    from mxnet_tpu.compile_cache import jax_cache

    cache = jax_cache.configure()
    import jax
    import numpy as np

    phases.done("imports")
    devices = jax.devices()
    phases.done("devices")
    platform = devices[0].platform
    if not args.rehearse and (platform != "tpu"
                              or len(devices) != cell.chips):
        print(f"benchmark: cell {cell.name} measures on exactly "
              f"{cell.chips} TPU chip(s); jax.devices() is {devices}. "
              "Nothing is measured on another platform or device count.",
              file=sys.stderr)
        return 2
    devices = devices[:cell.chips]

    from harness import peaks
    from mxnet_tpu.parallel.spmd import step_compile_stats

    peak = None if args.rehearse else peaks.peak(devices[0].device_kind)
    n = cell.traffic["batch"]
    stats0, jax0 = step_compile_stats(), cache.counts()
    trainer, batch, checks, facts = set_up(cell, args.seed, devices, phases)
    stats1, jax1 = step_compile_stats(), cache.counts()
    checks["one_step_program_in_setup"] = built(stats1) - built(stats0) == 1
    step_compile_s = stats1["seconds_total"] - stats0["seconds_total"]
    setup_s = time.perf_counter() - T_PROCESS
    info(cell=cell.name, seed=args.seed, batch=n, chips=cell.chips,
         platform=platform, setup_s=setup_s, setup_phases_s=phases.seconds,
         step_compile_s=step_compile_s,
         jax_cache={k: jax1[k] - jax0[k] for k in jax1},
         jax_cache_dir=cache.directory, **facts)

    def step():
        return trainer.step(*batch)

    if args.trace:
        win, trace = traced_window(step, cell.name)
    else:
        win, trace = window.run(step, seconds=args.seconds), None
    checks["no_step_program_in_window"] = \
        built(step_compile_stats()) == built(stats1)
    checks["every_loss_finite"] = win.failed == 0
    checks["parameters_on_the_chips"] &= on_the_chips(
        trainer.params.values(), devices)

    memory_peak, memory_facts = memory_counters(trainer, devices)
    run = {
        "chips": cell.chips, "samples_per_step": n,
        "setup_s": setup_s, "window": win, "trace": trace,
        "flops_per_sample": cell.model.flops_per_sample(cell.config,
                                                        cell.traffic),
        "peak": peak, "step_compile_s": step_compile_s,
        "memory_peak_bytes": memory_peak,
    }
    periods = np.diff(win.done_at_s)    # completion to completion
    info(attempted=win.attempted, completed=win.completed,
         failed=win.failed, errors=win.errors[:3],
         window_s=win.seconds, last_loss=win.losses[-1:] or None,
         **({} if args.rehearse else rates(run, cell.traffic)),
         step_ms_quartiles=[1e3 * float(q) for q in np.percentile(
             periods, [25, 50, 75])] if len(periods) else None,
         host_step_call_ms_median=1e3 * float(np.median(win.step_call_s))
         if win.step_call_s else None,
         samples_unit=cell.model.SAMPLES_UNIT,
         flops_per_sample=run["flops_per_sample"], **memory_facts,
         checks=checks)

    metrics = {}
    for m in cell.per_layer if args.trace else cell.end_to_end:
        # a rehearsal's numbers are a CPU's: every metric is null
        value = None if args.rehearse else lookup.metric_reader(
            "layer_metrics" if args.trace else "e2e_metrics",
            m["name"])(run)
        if value is not None or args.rehearse:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        breakdown = trace.breakdown()
    print(result.line(correct=all(checks.values()), attempted=win.attempted,
                      failed=win.failed, metrics=metrics, device=device,
                      breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
