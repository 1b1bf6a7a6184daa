"""The faults evabyte's `reference_check` limits have to catch, read at
the cell's real size on the chip and judged as benchmark/run.py's set_up
judges a run: the same `rel_l2`, the committed tolerances, the same names
of checks, `correct` = all of them.  (set_up's comparison is written in
line and cannot be called apart, so the two expressions are repeated
here, as in laguna_precision_readings.py.)  Each control has to come out
`"correct": false`; the system's own readings over its seeds are the
other side of each limit, and both are in PERF.md.  A builder's tool,
never part of a run.

    python benchmark/tools/evabyte_precision_readings.py [--seed N]
        [--rehearse]    (the rehearsal sizes on the CPU: a dry run)

`fp8_weights`: reference.py (float32 at matmul precision "highest"
throughout) with every matrix (projections, MLP, embedding, head, phi
and mu) rounded to float8_e4m3fn, the nearest precision below the
configuration's bfloat16, stands where the system stands: its logits on
the seeded sample and its loss on that sample against the unrounded
reference's.  The contract asks that one of the cell's limits refuses
it, not each.
`no_remote_term`: the reference with every summary masked out of
`eva_attention` (a query sees its own window's keys alone: what a
windowed-attention kernel would compute) stands where the system
stands: the mechanism this configuration exists for, left out.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

from harness import lookup  # noqa: E402

CELL = "evabyte_s32768"


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
    trainer = model.build(args.seed, config, cell.traffic, cell.chips)
    params = run.reference_params(trainer)
    del trainer                     # the state, off the chip again
    tokens = model.sample(args.seed, config, cell.traffic)[0]

    def evaluate(params, **how):
        scores = reference.logits(params, tokens, config, **how)
        return scores, reference.loss_of(scores, tokens)

    def host(scores, loss):
        return np.asarray(scores, np.float32), float(loss)

    want, want_loss = host(*jax.jit(evaluate)(params))

    def judged(got, got_loss):
        got, got_loss = host(got, got_loss)
        error = run.rel_l2(got, want)
        checks = {
            "logits_agree_with_reference": bool(
                np.isfinite(got).all()
                and error <= tol["logits_rel_l2_tol"]),
            "first_loss_agrees_with_reference":
                abs(got_loss - want_loss) <= tol["first_loss_abs_tol"]}
        return {"logits_rel_l2": error, "loss": got_loss,
                "loss_abs_diff": abs(got_loss - want_loss),
                "checks": checks, "correct": all(checks.values())}

    no_remote = judged(*jax.jit(
        lambda p: evaluate(p, keep_remote=False))(params))
    # array by array and in two steps: inside ONE program XLA on the TPU
    # takes a convert to float8 and back for nothing and drops it (PR 31)
    rounded = {k: v.astype(jnp.float8_e4m3fn) if v.ndim >= 2 else v
               for k, v in params.items()}
    del params
    rounded = {k: v.astype(jnp.float32) for k, v in rounded.items()}
    print(json.dumps({
        "platform": jax.devices()[0].platform, "seed": args.seed,
        "tolerances": {k: tol[k] for k in ("logits_rel_l2_tol",
                                           "first_loss_abs_tol")},
        "reference_loss": want_loss,
        "fp8_weights": judged(*jax.jit(evaluate)(rounded)),
        "no_remote_term": no_remote}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
