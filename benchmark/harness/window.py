"""The measured window: training steps dispatched with a fixed run-ahead.

JAX returns from a step before the device has finished it, so a loop
that only dispatches measures the enqueue.  Here the host blocks on the
loss of step i-RUN_AHEAD before it dispatches step i: the device always
has work queued (it never waits for the host unless the host takes longer
than a whole step), the host cannot queue the whole window, and each
step's completion is observed.  The window's clock stops at the last
`block_until_ready`.
"""
from __future__ import annotations

import contextlib
import math
import time
from collections import deque
from dataclasses import dataclass, field

RUN_AHEAD = 2


@dataclass
class Window:
    seconds: float = 0.0          # first dispatch to last completion
    attempted: int = 0            # steps dispatched
    completed: int = 0            # steps whose loss came back finite
    failed: int = 0               # steps that raised or gave a non-finite loss
    errors: list = field(default_factory=list)
    losses: list = field(default_factory=list)        # every loss read back
    step_call_s: list = field(default_factory=list)   # host time in step()
    done_at_s: list = field(default_factory=list)     # completion, from t0


def run(step, *, seconds=None, steps=None, span=None) -> Window:
    """Call `step()` (returns the loss, not yet computed) until `seconds`
    have passed or `steps` were dispatched, whichever is given.  `span`,
    when given, is a context-manager factory (`jax.profiler.
    TraceAnnotation`) put around each call and each wait."""
    span = span or (lambda name: contextlib.nullcontext())
    w = Window()
    pending = deque()
    t0 = time.perf_counter()

    def collect():
        loss = pending.popleft()
        try:
            with span("bench.block"):
                value = float(loss.asnumpy())
        except Exception as e:          # noqa: BLE001 - counted, reported
            w.failed += 1
            w.errors.append(f"block: {type(e).__name__}: {e}")
            return False
        w.done_at_s.append(time.perf_counter() - t0)
        w.losses.append(value)
        if math.isfinite(value):
            w.completed += 1
        else:
            w.failed += 1
            w.errors.append(f"non-finite loss {value}")
        return True

    def more() -> bool:
        if steps is not None:
            return w.attempted < steps
        return time.perf_counter() - t0 < seconds

    while more():
        if len(pending) >= RUN_AHEAD and not collect():
            break
        t = time.perf_counter()
        w.attempted += 1
        try:
            with span("bench.step_call"):
                pending.append(step())
        except Exception as e:          # noqa: BLE001 - counted, reported
            w.failed += 1
            w.errors.append(f"step: {type(e).__name__}: {e}")
            break
        w.step_call_s.append(time.perf_counter() - t)
    while pending and collect():
        pass
    # a step that raised took its successors' inputs with it
    w.failed += len(pending)
    w.seconds = time.perf_counter() - t0
    return w
