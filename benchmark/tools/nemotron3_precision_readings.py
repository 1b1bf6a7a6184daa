"""How far a lower precision moves nemotron3_super_120b's reference, at
the cell's real size on the chip: the second of the two readings its
`reference_check` tolerances lie between (the first is what the system
itself measures over its seeds; both are in PERF.md).  A builder's tool,
never part of a run.

    python benchmark/tools/nemotron3_precision_readings.py [--seed N]

Three evaluations of reference.py on one seeded sample, float32 at
matmul precision "highest" throughout except for the one thing named:

  float32      the reference as the cell uses it
  fp8_weights  every matrix (projections, embedding, head, experts,
               convolution) rounded to float8_e4m3fn: the nearest
               precision below the configuration's bfloat16
  bf16_state   the scan's carried state rounded to bfloat16 after every
               position

and for the last two the relative L2 error of the logits and the
distance of the loss from the float32 evaluation: each has to come out
beyond the cell's tolerance.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

from harness import lookup  # noqa: E402

CELL = "nemotron3_super_s8192"


class _RoundedCarry:
    """`jax.lax` with a `scan` that rounds the carry to bfloat16, by
    `reduce_precision`: XLA elides a float32 -> bfloat16 -> float32
    convert pair inside one program (excess precision is allowed)."""

    def __getattr__(self, name):
        import jax

        return getattr(jax.lax, name)

    def scan(self, step, init, xs):
        import jax

        def rounded(carry, x):
            carry, y = step(carry, x)
            return jax.lax.reduce_precision(carry, exponent_bits=8,
                                            mantissa_bits=7), y
        return jax.lax.scan(rounded, init, xs)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import run      # benchmark/run.py: reference_params, rel_l2

    cell = lookup.cell(CELL)
    config, reference = cell.config, cell.reference
    trainer = cell.model.build(args.seed, config, cell.traffic, cell.chips)
    params = run.reference_params(trainer)
    del trainer                     # the state's 7 GB, off the chip again
    tokens = cell.model.sample(args.seed, config, cell.traffic)[0]

    def evaluate(params):
        scores, loss = jax.jit(lambda p, t: (
            lambda s: (s, reference.loss_of(s, t)))(
                reference.logits(p, t, config)))(params, tokens)
        return np.asarray(scores, np.float32), float(loss)

    want, want_loss = evaluate(params)
    readings = {"platform": jax.devices()[0].platform,
                "float32": {"loss": want_loss}}
    rounded = {k: v.astype(jnp.float8_e4m3fn).astype(jnp.float32)
               if v.ndim >= 2 else v for k, v in params.items()}
    real_lax = reference.lax
    for name, p, lax in (("fp8_weights", rounded, real_lax),
                         ("bf16_state", params, _RoundedCarry())):
        reference.lax = lax
        try:
            got, loss = evaluate(p)
        finally:
            reference.lax = real_lax
        readings[name] = {"logits_rel_l2": run.rel_l2(got, want),
                          "loss": loss,
                          "loss_abs_diff": abs(loss - want_loss)}
    print(json.dumps(readings), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
