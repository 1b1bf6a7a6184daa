"""The faults lfm2_8b_a1b's `reference_check` limits have to catch, read
at the cell's real size on the chip and judged as benchmark/run.py's
set_up judges a run: the same `rel_l2`, the committed tolerances, the
same names of checks, `correct` = all of them.  (set_up's comparison is
written in line and cannot be called apart, so the two expressions are
repeated here, as the four siblings' tools repeat them.)  Each control
has to come out `"correct": false`; the system's own readings over its
seeds are the other side of each limit, and both are in PERF.md.  A
builder's tool, never part of a run.

    python benchmark/tools/lfm2_precision_readings.py [--seed N]
        [--rehearse]    (the rehearsal sizes on the CPU: a dry run)

Each control is reference.py (float32 at matmul precision "highest"
throughout) with one fault, standing where the system stands: what
`reference_logits` hands out on the seeded sample (the logits, and the
first attention layer's operator output) and its loss on that sample
against the faultless reference's.  The contract asks that one of the
cell's limits refuses each, not both.
`fp8_weights`: every matrix (projections, taps, embedding = head,
experts, router) rounded to float8_e4m3fn, the nearest precision below
the configuration's bfloat16.
`taps_reversed`: every conv layer's taps in the other order (tap 0 on
the current position).
`no_qk_norm`: the RMSNorm a head of q and k left out.  At the cell's
init q and k reach that norm at an RMS of 0.9 and its gain is one, so
thirteen layers on the fault moves the logits by no more than bfloat16
moves the system's; the operator's own output holds it whole, and that
entry is the one that has to refuse it.  `system_without_qk_norm` is
the same fault in the SYSTEM: the cell's trainer built with the zoo's
`Lfm2Layer._head_norm` replaced by the identity for this one build,
judged on the sample against the faultless reference as a run is.
`no_rotation`: the rotation of q and k left out.
`half_batch`: a step that trained on the first of the batch's two
sequences alone: the reference's loss on that sequence stands where the
step's first loss stands, against the reference's on the whole batch.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

from harness import lookup  # noqa: E402

CELL = "lfm2_8b_a1b_s8192"


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

    from mxnet_tpu.gluon.model_zoo import lfm2

    config, reference, model = cell.config, cell.reference, cell.model
    tol = config["reference_check"]
    tokens = model.sample(args.seed, config, cell.traffic)[0]
    head_norm = lfm2.Lfm2Layer._head_norm
    lfm2.Lfm2Layer._head_norm = lambda self, F, x, weight, heads: x
    try:
        trainer = model.build(args.seed, config, cell.traffic, cell.chips)
        faulty = model.system_logits(trainer, (tokens,), config)
    finally:
        lfm2.Lfm2Layer._head_norm = head_norm
    params = run.reference_params(trainer)      # the seed's own weights
    del trainer                     # the state's 8 GB, off the chip again
    batch = model.batch(args.seed, config, cell.traffic, lambda a: a)

    def evaluate(params, tokens, **patched):
        """-> (what reference_logits hands out, loss) of the reference
        with `patched` functions."""
        saved = {k: getattr(reference, k) for k in patched}
        for k, f in patched.items():
            setattr(reference, k, f)
        model._reference_program.cache_clear()      # traced with `saved`
        try:
            scores, operator, loss = model._reference(
                reference, params, tokens, config)
            return {"lm": np.asarray(scores, np.float32),
                    "attention_operator": np.asarray(operator, np.float32)
                    }, float(loss)
        finally:
            for k, f in saved.items():
                setattr(reference, k, f)
            model._reference_program.cache_clear()

    want, want_loss = evaluate(params, tokens)
    by_sequence = [evaluate(params, row[None])[1] for row in batch[0]]
    batch_loss = float(np.mean(by_sequence))

    def judged(readings, checks):
        return {**readings, "checks": checks,
                "correct": all(checks.values())}

    def loss_check(stand_in, truth):
        return {"first_loss_agrees_with_reference": bool(
            abs(stand_in - truth) <= tol["first_loss_abs_tol"])}

    def logits_check(got):
        """set_up's: every entry finite and within the one tolerance."""
        errors = {k: run.rel_l2(got[k], want[k]) for k in want}
        return errors, {"logits_agree_with_reference": bool(all(
            np.isfinite(got[k]).all() and e <= tol["logits_rel_l2_tol"]
            for k, e in errors.items()))}

    def control(params, **patched):
        got, got_loss = evaluate(params, tokens, **patched)
        errors, check = logits_check(got)
        return judged(
            {"reference_rel_l2": errors, "loss": got_loss,
             "reference_loss": want_loss,
             "loss_abs_diff": abs(got_loss - want_loss)},
            {**check, **loss_check(got_loss, want_loss)})

    errors, check = logits_check(faulty)
    readings = {
        "system_without_qk_norm": judged({"reference_rel_l2": errors},
                                         check),
        "taps_reversed": control({
            k: v[:, ::-1] if k.endswith("conv_weight") else v
            for k, v in params.items()}),
        "no_qk_norm": control(params, head_norm=lambda x, w, eps: x),
        "no_rotation": control(params, rotate=lambda x, theta: x),
        "half_batch": judged(
            {"reference_loss_by_sequence": by_sequence,
             "loss_abs_diff": abs(by_sequence[0] - batch_loss)},
            loss_check(by_sequence[0], batch_loss))}
    # array by array and in two steps: inside ONE program XLA on the TPU
    # takes a convert to float8 and back for nothing and drops it (read
    # on the chip, PR 31: the "rounded" logits came back 0.0 off)
    rounded = {k: v.astype(jnp.float8_e4m3fn) if v.ndim >= 2 else v
               for k, v in params.items()}
    del params
    rounded = {k: v.astype(jnp.float32) for k, v in rounded.items()}
    readings["fp8_weights"] = control(rounded)
    print(json.dumps({
        "platform": jax.devices()[0].platform, "seed": args.seed,
        "tolerances": {k: tol[k] for k in ("logits_rel_l2_tol",
                                           "first_loss_abs_tol")},
        **readings}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
