"""The third rehearsal: compile a cell's step program at its real size
for a v5e that is described, not attached, and print the bytes it plans
on each chip.  No chip time; nothing runs, so this gives no time and no
result.  It is how a cell's batch is chosen (the largest that stays under
the share of device memory PERF.md names) before its first chip call.

    JAX_PLATFORMS=cpu python benchmark/tools/aot_step_bytes.py \
        --workload bert_base_s512 [--batch 24 32 40]

SPMDTrainer places its parameters as it is built, and a described device
holds no array, so this script keeps the parameters on the CPU, hands the
trainer a mesh of the described devices, and lowers the trainer's own
pure step (`_build_pure`, with the shardings `_get_step` would give it)
from shapes.  It reaches into the trainer's private parts for that: a
builder's tool, never part of a run.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

from harness import lookup  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, nargs="*",
                    help="global batches to try (default: the cell's)")
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu import parallel
    from mxnet_tpu import random as rnd
    from mxnet_tpu.parallel import spmd

    jax.config.update("jax_enable_compilation_cache", False)
    cell = lookup.cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)[:cell.chips]
    real_make_mesh = parallel.make_mesh
    parallel.make_mesh = lambda **kw: real_make_mesh(devices=devices, **kw)
    spmd._global_put = lambda v, sh: v      # parameters stay where they are
    trainer = cell.model.build(0, cell.config, cell.traffic, cell.chips)
    mesh = trainer.mesh

    def shaped(tree, shardings):
        return jax.tree_util.tree_map(
            lambda v, sh: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                               sharding=sh),
            tree, shardings)

    repl = NamedSharding(mesh.mesh, P())
    psh = trainer._shardings
    ssh = {n: tuple(trainer._state_shardings[n] for _ in s)
           for n, s in trainer.opt_state.items()}
    n_lab = trainer.n_labels
    for batch in args.batch or [cell.traffic["batch"]]:
        traffic = dict(cell.traffic, batch=batch)
        arrays = cell.model.batch(0, cell.config, traffic, _shape_only)
        data = tuple(jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=parallel.shard_batch(
                mesh, extra_dims=len(a.shape) - 1)) for a in arrays)
        inputs, labels = (data, ()) if n_lab == 0 else \
            (data[:-n_lab], data[-n_lab:])
        key = rnd.next_key()

        def scalar(dtype):
            return jax.ShapeDtypeStruct((), dtype, sharding=repl)

        lowered = jax.jit(
            trainer._build_pure(),
            in_shardings=(psh, ssh, None, None, repl, repl, repl),
            out_shardings=(psh, ssh, repl, None),
            donate_argnums=(0, 1)).lower(
                shaped(trainer.params, psh), shaped(trainer.opt_state, ssh),
                inputs, labels,
                jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=repl),
                scalar(np.float32), scalar(np.int32))
        try:
            m = lowered.compile().memory_analysis()
        except Exception as e:      # noqa: BLE001 - the compiler's verdict
            print(f"batch {batch}: refused: {str(e)[:300]}", flush=True)
            continue
        need = m.argument_size_in_bytes + m.temp_size_in_bytes
        print(f"batch {batch}: per chip arguments "
              f"{m.argument_size_in_bytes / 2**30:.3f} GiB + temporaries "
              f"{m.temp_size_in_bytes / 2**30:.3f} GiB = "
              f"{need / 2**30:.3f} GiB "
              f"(outputs {m.output_size_in_bytes / 2**30:.3f}, aliased "
              f"{m.alias_size_in_bytes / 2**30:.3f})", flush=True)
    return 0


def _shape_only(x, *args):
    """`put` for model.batch: shapes, nothing placed."""
    import jax
    import numpy as np

    if callable(x):
        return jax.eval_shape(x, *args)
    return np.asarray(x)


if __name__ == "__main__":
    sys.exit(main())
