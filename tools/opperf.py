"""Per-op micro-benchmark harness (counterpart of the reference's
benchmark/opperf/ — per-operator forward/backward latency so op-level
perf regressions show up in artifact diffs, SURVEY.md §6).

For each covered op, three timings (median-of-runs, µs/call):
  * eager   — the imperative NDArray path (CS1: python dispatch +
              registry invoke + async jax dispatch), fwd only
  * jit_fwd — the op compiled alone via jax.jit (what a traced program
              pays, minus fusion with neighbors)
  * jit_bwd — compiled VJP application (fwd+bwd program)

Run on CPU (pinned, for regression diffs) or TPU (the real numbers):
    python tools/opperf.py --out OPPERF.json          # current backend
    JAX_PLATFORMS=cpu python tools/opperf.py
    python tools/opperf.py --ops Convolution,dot      # subset

The committed OPPERF.json is the baseline; CI-style usage is to re-run
and diff `value` columns (>2x swings on the same backend are real).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _specs(np, large):
    """op name -> (args, attrs). Shapes: `large` on accelerators
    (bandwidth-visible), small on CPU (keeps the sweep under a minute).
    Covers the hot families: MXU ops, normalization, elementwise,
    reductions, indexing, optimizer updates, vision/detection."""
    r = np.random.RandomState(0)

    def f(*shape):
        return r.rand(*shape).astype(np.float32)

    B, C, H = (64, 128, 56) if large else (8, 32, 14)
    S, U = (128, 768) if large else (16, 64)
    N = (1024, 4096) if large else (128, 256)
    sp = {
        # MXU
        "FullyConnected": ((f(B, N[0]), f(N[1], N[0]), f(N[1])),
                           {"num_hidden": N[1]}),
        "dot": ((f(N[0], N[0]), f(N[0], N[0])), {}),
        "batch_dot": ((f(16, S, 64), f(16, 64, S)), {}),
        "Convolution": ((f(B, C, H, H), f(C, C, 3, 3)),
                        {"kernel": (3, 3), "pad": (1, 1), "num_filter": C,
                         "no_bias": True}),
        "Deconvolution": ((f(B, C, H // 2, H // 2), f(C, C, 2, 2)),
                          {"kernel": (2, 2), "stride": (2, 2),
                           "num_filter": C, "no_bias": True}),
        # normalization / activation
        "BatchNorm": ((f(B, C, H, H), f(C), f(C), f(C), f(C) + 1.0),
                      {"_train": True}),
        "LayerNorm": ((f(B, S, U), f(U), f(U)), {"axis": -1}),
        "softmax": ((f(B, S, S),), {"axis": -1}),
        "log_softmax": ((f(B, N[1]),), {"axis": -1}),
        "Activation": ((f(B, C, H, H),), {"act_type": "relu"}),
        "LeakyReLU": ((f(B, C, H, H),), {"act_type": "leaky"}),
        # elementwise / broadcast
        "broadcast_add": ((f(B, C, H, H), f(1, C, 1, 1)), {}),
        "broadcast_mul": ((f(B, C, H, H), f(1, C, 1, 1)), {}),
        "elemwise_add": ((f(B, C, H, H), f(B, C, H, H)), {}),
        "exp": ((f(B, C, H, H),), {}),
        "sqrt": ((f(B, C, H, H) + 1.0,), {}),
        "clip": ((f(B, C, H, H),), {"a_min": 0.1, "a_max": 0.9}),
        # reductions / shape
        "sum": ((f(B, C, H, H),), {"axis": (0, 2, 3)}),
        "mean": ((f(B, C, H, H),), {"axis": (0, 2, 3)}),
        "max": ((f(B, C, H, H),), {"axis": (2, 3)}),
        "argsort": ((f(B, N[0]),), {"axis": -1}),
        "transpose": ((f(B, C, H, H),), {"axes": (0, 2, 3, 1)}),
        "Reshape": ((f(B, C, H, H),), {"shape": (B, C * H * H)}),
        "concat": ((f(B, C, H, H), f(B, C, H, H)), {"dim": 1}),
        "slice": ((f(B, C, H, H),),
                  {"begin": (0, 0, 1, 1), "end": (B, C, H - 1, H - 1)}),
        # indexing / embedding
        "take": ((f(N[1], U), r.randint(0, N[1], (B, S)).astype("int32")),
                 {}),
        "Embedding": ((r.randint(0, N[1], (B, S)).astype("int32"),
                       f(N[1], U)),
                      {"input_dim": N[1], "output_dim": U}),
        "one_hot": ((r.randint(0, N[0], (B * 8,)).astype("int32"),),
                    {"depth": N[0]}),
        "gather_nd": ((f(N[0], N[0]),
                       r.randint(0, N[0], (2, 64)).astype("int32")), {}),
        # pooling
        "Pooling": ((f(B, C, H, H),),
                    {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"}),
        # loss-ish
        "smooth_l1": ((f(B, N[0]),), {"scalar": 1.0}),
        "SoftmaxOutput": ((f(B, N[0]),
                           r.randint(0, N[0], (B,)).astype("float32")), {}),
        # optimizer updates (fwd only — not differentiable)
        "sgd_mom_update": ((f(N[1], N[0]), f(N[1], N[0]), f(N[1], N[0])),
                           {"lr": 0.1, "momentum": 0.9, "wd": 1e-4}),
        "adam_update": ((f(N[1], N[0]), f(N[1], N[0]), f(N[1], N[0]),
                         f(N[1], N[0])),
                        {"lr": 1e-3, "beta1": 0.9, "beta2": 0.999,
                         "epsilon": 1e-8, "wd": 0.0}),
        # vision / detection
        "BilinearResize2D": ((f(B, C, H, H),),
                             {"height": H * 2, "width": H * 2}),
        "box_iou": ((f(256, 4), f(256, 4)), {"format": "corner"}),
        "box_nms": ((np.concatenate(
            [r.rand(1, 512, 1), r.rand(1, 512, 1),
             np.sort(r.rand(1, 512, 4), -1)], -1).astype(np.float32),),
            {"overlap_thresh": 0.5, "topk": 100}),
        # ---- hot-family widening (round-4 verdict item #4) ----
        # Convolution variants: the ResNet bottleneck trio (1x1 project,
        # stride-2 downsample) + depthwise grouping
        "Convolution@1x1": ((f(B, C * 2, H // 2, H // 2),
                             f(C * 2, C * 2, 1, 1)),
                            {"kernel": (1, 1), "num_filter": C * 2,
                             "no_bias": True}),
        "Convolution@s2": ((f(B, C, H, H), f(C * 2, C, 3, 3)),
                           {"kernel": (3, 3), "stride": (2, 2),
                            "pad": (1, 1), "num_filter": C * 2,
                            "no_bias": True}),
        "Convolution@dw": ((f(B, C, H, H), f(C, 1, 3, 3)),
                           {"kernel": (3, 3), "pad": (1, 1),
                            "num_filter": C, "num_group": C,
                            "no_bias": True}),
        # fused RNN op (scan-based lstm/gru) on a BERT-ish shape
        "RNN@lstm": ((f(S, B // 2, U // 2),
                      f(_rnn_psize("lstm", U // 2, U // 2, 1, False))),
                     {"state_size": U // 2, "num_layers": 1,
                      "mode": "lstm"}),
        "RNN@gru": ((f(S, B // 2, U // 2),
                     f(_rnn_psize("gru", U // 2, U // 2, 1, False))),
                    {"state_size": U // 2, "num_layers": 1,
                     "mode": "gru"}),
        # fused attention (the Pallas kernel on TPU, XLA fallback on CPU)
        "dot_product_attention": ((f(B // 4, S, U), f(B // 4, S, U),
                                   f(B // 4, S, U),
                                   np.ones((B // 4, S), np.float32)),
                                  {"num_heads": U // 64}),
        "dot_product_attention@causal": (
            (f(B // 4, S, U), f(B // 4, S, U), f(B // 4, S, U),
             np.ones((B // 4, S), np.float32)),
            {"num_heads": U // 64, "causal": True}),
        # fused Conv+BN+ReLU Pallas unit (XLA fallback on CPU) — NHWC
        "FusedConvUnit": ((f(B, H, H, C), f(C, C, 3, 3), f(C) + 0.5,
                           f(C), f(C)),
                          {"kernel": (3, 3), "pad": (1, 1),
                           "act_in": True, "want_stats": True}),
        # remaining optimizer hot path
        "lamb_update_phase1": ((f(N[1], N[0]), f(N[1], N[0]),
                                f(N[1], N[0]), f(N[1], N[0])),
                               {"beta1": 0.9, "beta2": 0.999,
                                "epsilon": 1e-6, "wd": 0.01, "t": 1}),
        "multi_sgd_update": ((f(N[0], N[0]), f(N[0], N[0])),
                             {"lrs": (0.1,), "wds": (1e-4,),
                              "num_weights": 1}),
        # second widening pass: masking, layout, more indexing/reduction
        # shapes the model zoo actually hits (Dropout is excluded: the
        # raw op takes a key the frontend threads — not harness-callable)
        "where": ((f(B, C, H, H), f(B, C, H, H), f(B, C, H, H)), {}),
        "tile": ((f(B, S),), {"reps": (1, 4)}),
        "SequenceMask": ((f(S, B, U),
                          (r.rand(B) * S).astype(np.float32)),
                         {"use_sequence_length": True, "value": 0.0}),
        "SwapAxis": ((f(B, S, U),), {"dim1": 0, "dim2": 1}),
        "pick": ((f(B, N[0]),
                  r.randint(0, N[0], (B,)).astype("float32")), {}),
        "topk": ((f(B, N[0]),), {"k": 5, "ret_typ": "value"}),
        "norm": ((f(B, C, H, H),), {"ord": 2}),
        "cumsum": ((f(B, N[0]),), {"axis": 1}),
        "sgd_update": ((f(N[1], N[0]), f(N[1], N[0])),
                       {"lr": 0.1, "wd": 1e-4}),
        "L2Normalization": ((f(B, U),), {"mode": "instance"}),
    }
    return sp


def _rnn_psize(mode, input_size, hidden, num_layers, bidirectional):
    import importlib
    rnn_ops = importlib.import_module("mxnet_tpu.ops.rnn")
    return rnn_ops.rnn_param_size(mode, input_size, hidden, num_layers,
                                  bidirectional)


def _time_call(fn, sync, repeat, number):
    """Median over `repeat` batches of `number` calls, µs/call."""
    best = []
    fn()  # warm (compile/caches)
    sync()
    for _ in range(repeat):
        t0 = time.perf_counter()
        for _ in range(number):
            out = fn()
        sync(out)
        best.append((time.perf_counter() - t0) / number)
    best.sort()
    return best[len(best) // 2] * 1e6


def compare(current, against_path, fail_over, floor_us=50.0,
            min_was_us=50.0, expect_all_baseline_rows=True):
    """Regression gate: every row in `against` that also ran now, same
    backend and shape, must not have slowed by more than `fail_over`
    (fraction) in its jit columns.

    Noise handling, calibrated against two same-code baselines on the
    1-core dev box (tools/opperf round-5): sub-50µs timings swing 2-3x
    run to run, so rows with a baseline under `min_was_us` are skipped
    and a regression must clear BOTH an absolute `floor_us` delta and
    the relative threshold.  With (50µs, 50µs, 2x) the gate flags zero
    false positives on identical code while still watching every
    MXU-scale op; tighter thresholds only make sense on an idle
    accelerator host.  Returns (regressions, compared_count)."""
    with open(against_path) as f:
        base = json.load(f)
    if base.get("backend") != current["backend"]:
        return [{"note": f"backend mismatch ({base.get('backend')} vs "
                 f"{current['backend']}) — comparison skipped"}], 0
    base_rows = {(r["op"], r.get("shape")): r for r in base["rows"]}
    regressions, compared = [], 0
    for row in current["rows"]:
        b = base_rows.get((row["op"], row.get("shape")))
        if b is None:
            continue
        for col in ("jit_fwd_us", "jit_bwd_us"):
            was, now = b.get(col), row.get(col)
            if not was or was < min_was_us:
                continue
            compared += 1
            if not now:
                # baseline-present / now-missing: the op regressed from
                # working to failing-to-compile-or-run — the worst kind
                # of regression, never a skip (ADVICE round 5)
                regressions.append(
                    {"op": row["op"], "col": col, "was_us": was,
                     "now_us": None,
                     "note": "timing present in baseline but missing "
                             "now (op no longer compiles/runs?)"})
                continue
            if now - was > floor_us and now > was * (1.0 + fail_over):
                regressions.append(
                    {"op": row["op"], "col": col, "was_us": was,
                     "now_us": now, "ratio": round(now / was, 2)})
    if expect_all_baseline_rows:
        # the complement of the loop above: a baseline op whose ROW is
        # entirely absent from the current sweep (spec dropped, sweep
        # crashed before reaching it) is the same working-to-not-
        # running-at-all class as a missing column — never a skip.
        # row_missing=True exempts these from the retry-confirm pass,
        # which cannot re-measure an op that produced no row.
        cur_keys = {(r["op"], r.get("shape")) for r in current["rows"]}
        for bkey, b in base_rows.items():
            if bkey in cur_keys:
                continue
            for col in ("jit_fwd_us", "jit_bwd_us"):
                was = b.get(col)
                if not was or was < min_was_us:
                    continue
                regressions.append(
                    {"op": b["op"], "col": col, "was_us": was,
                     "now_us": None, "row_missing": True,
                     "note": "row present in baseline but absent from "
                             "the current sweep (op dropped or no "
                             "longer runs)"})
    return regressions, compared




def run_rows(names, specs, args, backend, quiet=False):
    """Measure one row per spec name (the shared sweep body, also used
    by the retry-confirm pass with a subset of names)."""
    import numpy as np  # noqa: F401  (specs were built from the caller)
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ops.registry import get_op

    rows = []
    for name in names:
        if name not in specs:
            print(f"# no spec for {name}", file=sys.stderr)
            continue
        arrs, attrs = specs[name]
        # spec keys may carry an '@variant' suffix (e.g. Convolution@1x1)
        # naming a shape/attr configuration of the same registry op
        op_name = name.split("@")[0]
        op = get_op(op_name)
        jarrs = [jnp.asarray(a) for a in arrs]
        nds = [mx.nd.array(a) for a in arrs]

        def sync(out=None):
            if out is not None:
                jax.block_until_ready(out)

        row = {"op": name, "backend": backend,
               "shape": "x".join(str(a.shape) for a in arrs)}
        # eager (imperative NDArray dispatch; wait_to_read = CS1 sync)
        ndout = [None]

        # private attrs (_train, ...) are supplied by the nd wrapper
        # itself on the eager path
        eager_attrs = {k: v for k, v in attrs.items()
                       if not k.startswith("_")}

        def eager():
            o = getattr(mx.nd, op_name)(*nds, **eager_attrs)
            ndout[0] = o[0] if isinstance(o, (list, tuple)) else o
            return ndout[0]

        row["eager_us"] = round(_time_call(
            lambda: eager(), lambda o=None: ndout[0].wait_to_read(),
            args.repeat, args.number), 1)

        jfn = jax.jit(lambda *xs: op.fn(*xs, **attrs))
        try:
            row["jit_fwd_us"] = round(_time_call(
                lambda: jfn(*jarrs), sync, args.repeat, args.number), 1)
        except Exception as e:  # keep the row: a None column is the
            # signal the regression gate reports, a crashed sweep is a
            # silent skip of every later op
            row["jit_fwd_us"] = None
            row["fwd_note"] = str(e).splitlines()[0][:80]

        if row["jit_fwd_us"] is not None and op.differentiable:
            def scalar_fn(*xs):
                o = op.fn(*xs, **attrs)
                o = o[0] if isinstance(o, (list, tuple)) else o
                return jnp.sum(o.astype(jnp.float32))

            diff_idx = [i for i, a in enumerate(jarrs)
                        if a.dtype.kind == "f"]
            gfn = jax.jit(jax.grad(scalar_fn, argnums=tuple(diff_idx))) \
                if diff_idx else None
            if gfn is not None:
                try:
                    row["jit_bwd_us"] = round(_time_call(
                        lambda: gfn(*jarrs), sync, args.repeat,
                        args.number), 1)
                except Exception as e:  # non-diff attr combos
                    row["jit_bwd_us"] = None
                    row["bwd_note"] = str(e).splitlines()[0][:80]
        rows.append(row)
        if not quiet:
            print(json.dumps(row))
    return rows




def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=None,
                    help="comma-separated subset (default: all covered)")
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--number", type=int, default=10)
    ap.add_argument("--large", action="store_true",
                    help="accelerator-scale shapes (auto on non-CPU)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--against", default=None,
                    help="baseline OPPERF json: exit 1 if any op's jit "
                         "column regressed past --fail-over")
    ap.add_argument("--fail-over", type=float, default=1.0,
                    help="allowed slowdown fraction vs --against "
                         "(default 1.0 = 2x; sub-2x deltas are timer "
                         "noise on the 1-core dev box)")
    ap.add_argument("--no-retry", action="store_true",
                    help="skip the retry-confirm pass on flagged ops "
                         "(a regression is normally only reported if "
                         "it reproduces in a targeted re-measure)")
    args = ap.parse_args()

    import numpy as np
    import jax

    backend = jax.default_backend()
    large = args.large or backend != "cpu"
    specs = _specs(np, large)
    names = (args.ops.split(",") if args.ops else sorted(specs))

    rows = run_rows(names, specs, args, backend)

    artifact = {"when": time.strftime("%Y-%m-%d %H:%M:%S"),
                "backend": backend, "large_shapes": large,
                "repeat": args.repeat, "number": args.number,
                "rows": rows}
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(artifact, fh, indent=1)
    if args.against:
        # --ops runs a deliberate subset: absent baseline rows are then
        # expected, not a regression signal
        regressions, compared = compare(
            artifact, args.against, args.fail_over,
            expect_all_baseline_rows=args.ops is None)
        flagged = sorted({r["op"] for r in regressions if "op" in r})
        retried = []
        if flagged and not args.no_retry:
            # retry-confirm: a concurrent process (another build step,
            # another jax import) can slow a whole stretch of the
            # sweep 2-3x on a small box.  Re-measure ONLY the
            # flagged ops; transient contention clears, a real
            # regression persists in both measurements.
            retried = flagged
            retry_rows = run_rows([n for n in names if n in flagged],
                                  specs, args, backend, quiet=True)
            retry_art = dict(artifact, rows=retry_rows)
            retry_reg, _ = compare(retry_art, args.against,
                                   args.fail_over,
                                   expect_all_baseline_rows=False)
            # confirm on (op, COLUMN): fresh noise tripping a different
            # column of the same op must not rescue the original flag.
            # row_missing flags stand as-is: an op that produced no row
            # cannot be re-measured, so the retry cannot clear it.
            confirmed = {(r["op"], r["col"]) for r in retry_reg
                         if "op" in r}
            regressions = [r for r in regressions
                           if "op" not in r
                           or r.get("row_missing")
                           or (r["op"], r["col"]) in confirmed]
        print(json.dumps({"against": args.against, "compared": compared,
                          "fail_over": args.fail_over,
                          "retried": retried,
                          "regressions": regressions}))
        if any("op" in r for r in regressions):
            return 1
        if compared == 0:
            # fail CLOSED: a backend mismatch or zero overlapping rows
            # means the gate checked nothing — a silent no-op here would
            # let real regressions ship while the nightly stays green
            print(json.dumps({"error": "regression gate compared 0 "
                              "columns (backend mismatch or disjoint "
                              "row keys) — regenerate the baseline on "
                              "this backend"}))
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
