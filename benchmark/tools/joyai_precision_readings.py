"""The faults joyai_llm_flash's `reference_check` limits have to catch,
read at the cell's real size on the chip and judged as benchmark/run.py's
set_up judges a run: the same `rel_l2`, the committed tolerances, the
same names of checks, `correct` = all of them.  (set_up's comparison is
written in line and cannot be called apart, so the two expressions are
repeated here, as the three siblings' tools repeat them.)  Each control
has to come out `"correct": false`; the system's own readings over its
seeds are the other side of each limit, and both are in PERF.md.  A
builder's tool, never part of a run.

    python benchmark/tools/joyai_precision_readings.py [--seed N]
        [--rehearse]    (the rehearsal sizes on the CPU: a dry run)

`fp8_weights`: reference.py (float32 at matmul precision "highest"
throughout) with every matrix (projections, both low-rank chains, the
join, embedding, head, experts, router) rounded to float8_e4m3fn, the
nearest precision below the configuration's bfloat16, stands where the
system stands: both heads' logits on the seeded sample and its loss on
that sample against the unrounded reference's.  The contract asks that
one of the cell's limits refuses it, not each.
`half_batch`: a step that trained on the first of the batch's two
sequences alone: the reference's loss on that sequence stands where the
step's first loss stands, against the reference's on the whole batch.
`no_mtp_term`: a step that left the module's loss term out: the
reference's main term on the batch stands where the first loss stands.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

from harness import lookup  # noqa: E402

CELL = "joyai_llm_flash_s8192"


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
    weight = config["mtp_loss_weight"]
    trainer = model.build(args.seed, config, cell.traffic, cell.chips)
    params = run.reference_params(trainer)
    del trainer                     # the state's 7.4 GB, off the chip again
    tokens = model.sample(args.seed, config, cell.traffic)[0]
    batch = model.batch(args.seed, config, cell.traffic, lambda a: a)

    def evaluate(params, tokens):
        """-> ({head: logits}, (main term, module's term))"""
        main, ahead, terms = model._reference(reference, params, tokens,
                                              config)
        return ({"lm": np.asarray(main, np.float32),
                 "mtp": np.asarray(ahead, np.float32)},
                tuple(float(t) for t in terms))

    def joined(terms):
        return terms[0] + weight * terms[1]

    want, want_terms = evaluate(params, tokens)
    by_sequence = [evaluate(params, row[None])[1] for row in batch[0]]
    batch_terms = tuple(float(t) for t in np.mean(by_sequence, 0))
    # array by array and in two steps: inside ONE program XLA on the TPU
    # takes a convert to float8 and back for nothing and drops it (read
    # on the chip, PR 31: the "rounded" logits came back 0.0 off)
    rounded = {k: v.astype(jnp.float8_e4m3fn) if v.ndim >= 2 else v
               for k, v in params.items()}
    del params
    rounded = {k: v.astype(jnp.float32) for k, v in rounded.items()}
    got, got_terms = evaluate(rounded, tokens)

    def judged(readings, checks):
        return {**readings, "checks": checks,
                "correct": all(checks.values())}

    def loss_check(stand_in, truth):
        return {"first_loss_agrees_with_reference": bool(
            abs(stand_in - truth) <= tol["first_loss_abs_tol"])}

    errors = {k: run.rel_l2(got[k], want[k]) for k in want}
    print(json.dumps({
        "platform": jax.devices()[0].platform, "seed": args.seed,
        "tolerances": {k: tol[k] for k in ("logits_rel_l2_tol",
                                           "first_loss_abs_tol")},
        "fp8_weights": judged(
            {"logits_rel_l2": errors, "loss_terms": got_terms,
             "reference_loss_terms": want_terms,
             "loss_abs_diff": abs(joined(got_terms) - joined(want_terms))},
            {"logits_agree_with_reference": all(
                bool(np.isfinite(got[k]).all()
                     and e <= tol["logits_rel_l2_tol"])
                for k, e in errors.items()),
             **loss_check(joined(got_terms), joined(want_terms))}),
        "half_batch": judged(
            {"reference_loss_terms_by_sequence": by_sequence,
             "loss_abs_diff": abs(joined(by_sequence[0])
                                  - joined(batch_terms))},
            loss_check(joined(by_sequence[0]), joined(batch_terms))),
        "no_mtp_term": judged(
            {"reference_loss_terms": batch_terms,
             "loss_abs_diff": abs(batch_terms[0] - joined(batch_terms))},
            loss_check(batch_terms[0], joined(batch_terms)))}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
