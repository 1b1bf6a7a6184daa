"""The faults ouro_2_6b's `reference_check` limits have to catch, read at
the cell's real size on the chip and judged as benchmark/run.py's set_up
judges a run: the same `rel_l2`, the committed tolerances, the same
names of checks, `correct` = all of them.  (set_up's comparison is
written in line and cannot be called apart, so the two expressions are
repeated here, as the five siblings' tools repeat them.)  Each control
has to come out `"correct": false`; the system's own readings over its
seeds (bfloat16, the configuration) are the other side of each limit,
and both are in PERF.md.  A builder's tool, never part of a run.

    python benchmark/tools/ouro_precision_readings.py [--seed N]
        [--rehearse]    (the rehearsal sizes on the CPU: a dry run)
        [--system-dtype float32 --seq-len 2048]

Each control is reference.py (float32 at matmul precision "highest"
throughout) with one fault, standing where the system stands: what
`reference_logits` hands out on the seeded sample (the four exits'
logits and the exit distribution) and its objective on that sample
against the faultless reference's.  The contract asks that one of the
cell's limits refuses each, not both.
`fp8_weights`: every matrix (projections, MLPs, embedding, head) rounded
to float8_e4m3fn, the nearest precision below the configuration's
bfloat16.
`three_passes`: the loop left after three passes (`total_ut_steps` 3):
the fourth exit hands out the third's logits, the third takes the
remainder of the distribution and the fourth gets none.
`no_post_norms`: the two norms after the sub-layers left out
(h + attention(RMSNorm(h)), h + mlp(RMSNorm(h))).
`no_entropy_term`: the objective without its entropy bonus (beta 0): the
logits and the distribution are the faultless ones, so the first loss
alone can refuse it.
`--system-dtype float32`: the SYSTEM built with float32 weights and
judged on the sample against the reference as a run is (at `--seq-len`,
since four exits' float32 logits at 8192 do not fit beside a float32
Adam state): how much of the configuration's reading is bfloat16's.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

from harness import lookup  # noqa: E402

CELL = "ouro_2_6b_s8192"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--system-dtype")
    ap.add_argument("--seq-len", type=int)
    args = ap.parse_args()

    import run      # benchmark/run.py: rehearsal, reference_params, rel_l2

    cell = lookup.cell(CELL)
    if args.rehearse:
        run.rehearsal(cell)
    if args.seq_len:
        cell.traffic["seq_len"] = args.seq_len

    from mxnet_tpu.compile_cache import jax_cache

    jax_cache.configure()       # a run's reference program, found again
    import jax
    import jax.numpy as jnp
    import numpy as np

    config, reference, model = cell.config, cell.reference, cell.model
    tol = config["reference_check"]
    sample = model.sample(args.seed, config, cell.traffic)
    trainer = model.build(
        args.seed, dict(config, dtype=args.system_dtype or config["dtype"]),
        cell.traffic, cell.chips)
    system = model.system_logits(trainer, sample, config) \
        if args.system_dtype else None
    params = run.reference_params(trainer)      # the seed's own weights
    del trainer                     # the state, off the chip again

    def evaluate(params, config, **patched):
        """-> (what reference_logits hands out, objective) of the
        reference with `patched` functions, on the sample."""
        saved = {k: getattr(reference, k) for k in patched}
        for k, f in patched.items():
            setattr(reference, k, f)
        model._reference_programs.cache_clear()     # traced with `saved`
        try:
            return model._reference(reference, params, sample[0], config)
        finally:
            for k, f in saved.items():
                setattr(reference, k, f)
            model._reference_programs.cache_clear()

    want, want_loss = evaluate(params, config)

    def judged(readings, checks):
        return {**readings, "checks": checks,
                "correct": all(checks.values())}

    def logits_check(got):
        """set_up's: every entry finite and within the one tolerance."""
        errors = {k: run.rel_l2(got[k], want[k]) for k in want}
        return errors, {"logits_agree_with_reference": bool(all(
            np.isfinite(got[k]).all() and e <= tol["logits_rel_l2_tol"]
            for k, e in errors.items()))}

    def control(got, got_loss):
        errors, check = logits_check(got)
        return judged(
            {"reference_rel_l2": errors, "loss": got_loss,
             "reference_loss": want_loss,
             "loss_abs_diff": abs(got_loss - want_loss)},
            {**check, "first_loss_agrees_with_reference": bool(
                abs(got_loss - want_loss) <= tol["first_loss_abs_tol"])})

    readings = {}
    if system is not None:
        errors, check = logits_check(system)
        readings[f"system_{args.system_dtype}"] = judged(
            {"reference_rel_l2": errors}, check)
        del system

    def plain_layer(p, pre, h, cfg):
        eps = cfg["rms_norm_eps"]
        h = h + reference.attention(
            p, pre, reference.rms_norm(h, p[pre + "norm_weight"], eps), cfg)
        return h + reference.gated_mlp(
            reference.rms_norm(h, p[pre + "mlp_norm_weight"], eps),
            p[pre + "mlp_gate_weight"], p[pre + "mlp_up_weight"],
            p[pre + "mlp_down_weight"])

    readings["no_post_norms"] = control(
        *evaluate(params, config, layer=plain_layer))
    readings["no_entropy_term"] = control(
        *evaluate(params, dict(config, exit_entropy_beta=0.0)))
    steps = config["total_ut_steps"]
    short, short_loss = evaluate(params,
                                 dict(config, total_ut_steps=steps - 1))
    short[f"exit{steps}"] = short[f"exit{steps - 1}"]
    short["exit_pdf"] = np.pad(short["exit_pdf"], ((0, 0), (0, 0), (0, 1)))
    readings["three_passes"] = control(short, short_loss)
    del short
    # array by array and in two steps: inside ONE program XLA on the TPU
    # takes a convert to float8 and back for nothing and drops it (read
    # on the chip, PR 31: the "rounded" logits came back 0.0 off)
    rounded = {k: v.astype(jnp.float8_e4m3fn) if v.ndim >= 2 else v
               for k, v in params.items()}
    del params
    rounded = {k: v.astype(jnp.float32) for k, v in rounded.items()}
    readings["fp8_weights"] = control(*evaluate(rounded, config))
    print(json.dumps({
        "platform": jax.devices()[0].platform, "seed": args.seed,
        "seq_len": cell.traffic["seq_len"],
        "tolerances": {k: tol[k] for k in ("logits_rel_l2_tol",
                                           "first_loss_abs_tol")},
        **readings}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
