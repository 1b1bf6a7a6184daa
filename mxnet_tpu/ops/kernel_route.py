"""Which kernel runs: the one place that decides whether a call takes its
Pallas kernel or the XLA twin of the same function.  A route (a module of
`ops/`, `parallel/moe.py`) keeps what is its own: kernel, twin,
`supports(shape)`, two route names (`Kernel`), layout glue.  Here, once:

  * the two knobs: `MXNET_USE_PALLAS=0` selects the twins anywhere,
    `MXNET_PALLAS_INTERPRET=1` runs the kernels through the Pallas
    interpreter on any platform (`interpret()`: the `pallas_call`s ask too).
  * the mesh question and the admission rule (`admit`: knob, `supports`,
    mesh): GSPMD cannot partition a Mosaic call, so under a mesh of
    several devices a kernel runs only as one call a batch shard inside a
    `shard_map` (`per_batch_shard`), and only a kernel that can take a
    shard: a property of the kernel (`Kernel.mesh`), not an option.
  * the dispatch, the one `lax.platform_dependent` of `ops/` and
    `parallel/`: the kernel in a program LOWERED for the TPU (a
    cpu()-resident warm pass on a TPU host lowers for the CPU and takes the
    twin; the same call inside the TPU step takes the kernel) and anywhere
    under the interpreter; autodiff goes through the chosen branch.
    Nothing is probed, nothing latches: lowered for the TPU the kernel
    runs or the call raises with Mosaic's own message.
  * the count: one store keyed by (family, route), bumped where the branch
    is chosen, at TRACE time (once a compiled program, never per step):
    routes chosen, not kernels run.  `mx_attention_route_total{route}` and
    `mx_rotary_route_total{route}` are its exports, the four public
    `route_counts()` its views.  `ops/dropout_mask.py` keeps its count of
    the dropout sites traced here too (`mx_dropout_sites_total{generator}`,
    `site_counts()`): a generator is chosen as a route is.

`ops/pallas_convbn.py` stays outside on purpose (ROADMAP.md Queue 3 item 2).
"""
from __future__ import annotations

import functools
import math
import threading
from typing import NamedTuple, Optional

import jax

from ..telemetry import instruments as _instruments
from ..util import env
from . import residuals

# Which answers of `mesh_batch_axes` a kernel can take.
NO_MESH = "no_mesh"             # None alone: a bare Mosaic call
BATCH_SHARDS = "batch_shards"   # None, or one call a batch shard
ANY_MESH = "any_mesh"           # not asked (ROADMAP.md names both as debts)


class Kernel(NamedTuple):
    """What a route says of its kernel beside the kernel itself."""
    family: str             # whose `route_counts()` shows the two names
    route: str              # counted where the kernel is admitted
    twin: Optional[str]     # counted where it is not; None: a later route's
    mesh: str = NO_MESH


def interpret() -> bool:
    return env.get_bool("MXNET_PALLAS_INTERPRET")


def shared_kernel(*statics):
    """The decorated kernel call jitted, so that a model's layers trace
    and lower the kernel ONCE (~0.15 s each at every start of the process,
    compile-cache hit or not: 7 s of `setup_s` in PR 26 before this).  The
    interpreter switch is read per call and is part of the jit's key."""
    def wrap(fn):
        jitted = jax.jit(fn, static_argnames=statics + ("interpret",))

        @functools.wraps(fn)
        def call(*operands, **kw):
            return jitted(*operands, **kw, interpret=interpret())
        return call
    return wrap


def mesh_batch_axes(batch):
    """The active mesh (`with mesh:`, as `SPMDTrainer` holds it around the
    traced step) as a kernel sees it: None where no mesh of several
    devices is active; (jax mesh, batch axes) where dp / fsdp are the only
    axes that split anything and split `batch` evenly; False where the
    mesh splits otherwise (tp, sp, ...: the heads or the sequence may be
    sharded, and the XLA route is the one GSPMD can partition)."""
    from ..parallel.mesh import current_mesh

    m = current_mesh()
    if m is None or m.mesh.size == 1:
        return None
    axes = tuple(a for a in ("dp", "fsdp") if m.axis_sizes.get(a, 1) > 1)
    shards = math.prod(m.axis_sizes[a] for a in axes)
    if shards != m.mesh.size or batch % shards:
        return False
    return m.mesh, axes


def admit(kernel: Kernel, supports, batch):
    """How `kernel` runs for a call of `batch` leading rows whose shape
    the route `supports`: True (one bare call), (mesh, axes) (one call a
    batch shard: `BATCH_SHARDS` kernels alone) or False (it does not: the
    twin runs).  Counts nothing."""
    if not (env.get_bool("MXNET_USE_PALLAS") and supports):
        return False
    shard = None if kernel.mesh == ANY_MESH else mesh_batch_axes(batch)
    if shard is None:
        return True
    return kernel.mesh == BATCH_SHARDS and shard


def choose(kernel: Kernel, supports, batch, kept=None, times=1):
    """`admit`, counted `times`: the kernel's route where it is admitted,
    with `kept` = (values, bytes) that its forward rule names for the
    backward, else its twin's."""
    how = admit(kernel, supports, batch)
    if how:
        count(kernel.family, kernel.route, times, kept)
    elif kernel.twin is not None:
        count(kernel.family, kernel.twin, times)
    return how


def dispatch(kernel, twin, *operands, interpret):
    """`kernel(*operands)` lowered for the TPU or under `interpret` (this
    module's `interpret()`, or the static argument of a jit that keys on
    it), `twin(*operands)` elsewhere."""
    if interpret:
        return kernel(*operands)
    return jax.lax.platform_dependent(*operands, tpu=kernel, default=twin)


def per_batch_shard(fn, how, *operands, replicated=()):
    """`fn(first_row, *operands)` as `admit` said `how`: True, once from
    row 0; (mesh, batch axes), on every device's batch shard inside a
    `shard_map`, `first_row` the shard's first GLOBAL row.  Every operand
    and result leads with the batch but the operands at the positions
    `replicated` (never 0), which each device gets whole."""
    if how is True:
        return fn(0, *operands)
    from jax.sharding import PartitionSpec as P

    from ..parallel._compat import shard_map_unchecked

    mesh, axes = how
    rows = P(axes)

    def one_shard(*operands):
        return fn(jax.lax.axis_index(axes) * operands[0].shape[0], *operands)

    return shard_map_unchecked(
        one_shard, mesh=mesh,
        in_specs=tuple(P() if i in replicated else rows
                       for i in range(len(operands))),
        out_specs=rows)(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def counted_backward(x, family, key):
    """`x`, and `count(family, key)` where the backward of the program
    that holds it is traced: once a call that is differentiated, never
    for a call that is not.  Nothing in the lowered program."""
    return x


def _counted_backward_bwd(family, key, _, ct):
    count(family, key)
    return (ct,)


counted_backward.defvjp(lambda x, family, key: (x, None),
                        _counted_backward_bwd)


# (family, key) -> times chosen since import
_EXPORTS = {"attention": _instruments.attention_route_total,
            "rotary": _instruments.rotary_route_total,
            "dropout": _instruments.dropout_sites_total}
_counts = {}
_lock = threading.Lock()


def declare(family, keys):
    """`keys`, in this order, are what `counts(family)` shows."""
    with _lock:
        _counts.update(dict.fromkeys(((family, key) for key in keys), 0))


def count(family, key, times=1, kept=None):
    with _lock:
        _counts[family, key] += times
    if family in _EXPORTS:
        _EXPORTS[family](key).inc(times)
    if kept is not None:
        residuals.note(key, *kept)


def counts(family):
    """{key: times chosen while a program was traced} since import.
    Read it before and after to count."""
    with _lock:
        return {key: n for (f, key), n in _counts.items() if f == family}
