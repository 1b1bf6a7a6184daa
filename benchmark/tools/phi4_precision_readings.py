"""The faults phi4_mini_flash's `reference_check` limits have to catch,
read at the cell's real size on the chip and judged as benchmark/run.py's
set_up judges a run: the same `rel_l2`, the committed tolerances, the
same names of checks, `correct` = all of them.  (set_up's comparison is
written in line and cannot be called apart, so the two expressions are
repeated here, as the six siblings' tools repeat them.)  Each control
has to come out `"correct": false`; the system's own readings over its
seeds are the other side of each limit, and both are in PERF.md.  A
builder's tool, never part of a run.

    python benchmark/tools/phi4_precision_readings.py [--seed N]
        [--rehearse]    (the rehearsal sizes on the CPU: a dry run)

Each control is reference.py (float32 at matmul precision "highest"
throughout) with one fault, standing where the system stands: what
`reference_logits` hands out on the seeded sample (the logits; the
memory m, layer n / 2's scan output; and `scan`, that scan alone on the
inputs the SYSTEM's scan had, judged by its own `scan_rel_l2_tol`) and
its loss on that sample against the faultless reference's, on the
seed's own parameters as the cell builds them.  The contract asks that one of the cell's limits refuses
each, not every one.
`fp8_weights`: every matrix (projections, taps, embedding = head)
rounded to float8_e4m3fn, the nearest precision below the
configuration's bfloat16.
`bfloat16_scan_state`: the state of every selective scan rounded to
bfloat16 after every step, everything else float32: what a scan that
does not keep its state in float32 computes.
`lambda_dropped`: lam = 0 in every differential core: a1 alone, plain
softmax attention under the sub-norm.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

from harness import lookup  # noqa: E402

CELL = "phi4_mini_flash_s16384"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import run      # benchmark/run.py: rehearsal, reference_params, rel_l2

    cell = lookup.cell(CELL)
    if args.rehearse:
        run.rehearsal(cell)

    from mxnet_tpu.compile_cache import jax_cache

    jax_cache.configure()       # a run's reference program, found again
    import jax
    import jax.numpy as jnp
    import numpy as np

    config, reference, model = cell.config, cell.reference, cell.model
    tol = config["reference_check"]
    tokens = model.sample(args.seed, config, cell.traffic)[0]
    trainer = model.build(args.seed, config, cell.traffic, cell.chips)
    system = model.system_logits(trainer, (tokens,), config)
    params = run.reference_params(trainer)      # the seed's own weights
    del trainer                     # the state's 5.5 GB, off the chip again

    scan_inputs = model._scan_seen["inputs"]

    def evaluate(params, **fault):
        """-> (what reference_logits hands out, loss) of the reference
        under `fault` (reference.py's two keys)."""
        faulty = dict(config, **fault)
        scores, memory, loss = model._reference(reference, params, tokens,
                                                faulty)
        return {"lm": np.asarray(scores, np.float32),
                "memory": np.asarray(memory, np.float32),
                "scan": model.reference_scan(reference, params, faulty,
                                             scan_inputs)}, float(loss)

    want, want_loss = evaluate(params)

    def judged(got, got_loss=None):
        """set_up's: every entry finite and within the one tolerance,
        the loss within its own."""
        errors = {k: run.rel_l2(got[k], want[k]) for k in want}
        limit = {k: tol["scan_rel_l2_tol" if k == "scan"
                        else "logits_rel_l2_tol"] for k in want}
        checks = {"logits_agree_with_reference": bool(all(
            np.isfinite(got[k]).all() and e <= limit[k]
            for k, e in errors.items()))}
        readings = {"reference_rel_l2": errors}
        if got_loss is not None:
            checks["first_loss_agrees_with_reference"] = bool(
                abs(got_loss - want_loss) <= tol["first_loss_abs_tol"])
            readings.update(loss=got_loss, reference_loss=want_loss,
                            loss_abs_diff=abs(got_loss - want_loss))
        return {**readings, "checks": checks,
                "correct": all(checks.values())}

    readings = {
        "system": judged(system),
        "bfloat16_scan_state": judged(*evaluate(
            params, _scan_state_dtype="bfloat16")),
        "lambda_dropped": judged(*evaluate(params, _drop_lambda=True))}
    # array by array and in two steps: inside ONE program XLA on the TPU
    # takes a convert to float8 and back for nothing and drops it (read
    # on the chip, PR 31: the "rounded" logits came back 0.0 off)
    rounded = {k: v.astype(jnp.float8_e4m3fn) if v.ndim >= 2 else v
               for k, v in params.items()}
    del params
    rounded = {k: v.astype(jnp.float32) for k, v in rounded.items()}
    readings["fp8_weights"] = judged(*evaluate(rounded))
    print(json.dumps({
        "platform": jax.devices()[0].platform, "seed": args.seed,
        "tolerances": {k: tol[k] for k in (
            "logits_rel_l2_tol", "scan_rel_l2_tol", "first_loss_abs_tol")},
        **readings}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
