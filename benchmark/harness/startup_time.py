"""Set-up by phase, from the program's own records (PR 36).

`setup_s` runs from the first line of `run.py` to the first step of the
window.  The program keeps a record of every phase of its own in it
(`mxnet_tpu.telemetry.tracing.phase`; `docs/observability.md`, "Time to
first step"):

    mx.setup.import (child mx.setup.import.jax), mx.setup.init,
    mx.setup.cast, mx.setup.place,
    mx.build.trace / .lower / .backend   stats: program, site, and on
                                         backend origin: compiled | cache
    mx.step.first_dispatch

each `{"id", "name", "parent", "start", "end", "seconds", "calls",
"stats"}`, seconds on `perf_counter` from the moment `import mxnet_tpu`
began.  `startup_spans()` hands them out; `compile_cache.jax_cache.
seconds()` has JAX's own trace / lower / backend totals over every
program of the process, the benchmark's reference and batch draws
included.  What is read here:

  * a record's *self-seconds*: its `seconds` less its children's, so a
    phase entered inside another (a deferred init inside a trace) is
    counted once, under its own name;
  * `seconds(names, **stats)`: the self-seconds of the records whose name
    is one of `names` or lies under one (`mx.setup.import.jax` under
    `mx.setup.import`) and whose stats match, or None where there is no
    such record;
  * `top_level_s`: the `seconds` of the records without a parent.  With
    every record read by one metric, the metrics add up to it, and
    `setup_s` less it is what the program cannot see: the road to the
    chip, the configuration's own draws, the reference check, the
    warm-up steps' device time.

`read(run)` is what the readers in `layer_metrics/` call.  It gives None
from a program that keeps no such records (the parent of PR 36), and the
readers then return None: the metric is left out of the line.

**Adding a `program_counter` metric.**  The program exposes an accessor
(a function that returns what it counted); a helper here imports it inside
a `try` (`_from_the_program` below, `scope_time._from_the_program`), so
that the benchmark still runs against a program that lacks it; one reader
file `layer_metrics/<name>.py` calls the helper; one entry goes at the end
of `per_layer` in `BENCHMARK.json` with `"source": "program_counter"`.
`run.py` is not edited and puts no new key in `run` (the `_startup_time`
there is this helper's own memo): what the program counts is read from the
program, in the process that ran it.
"""
from __future__ import annotations

import json
from dataclasses import dataclass


def _from_the_program():
    """(startup_spans(), JAX's stage totals), or None from a program
    that has neither."""
    try:
        from mxnet_tpu.compile_cache import jax_cache
        from mxnet_tpu.telemetry.tracing import startup_spans
        return startup_spans(), jax_cache.seconds()
    except (ImportError, AttributeError):
        return None


def _matches(record, names, stats) -> bool:
    return any(record["name"] == n or record["name"].startswith(n + ".")
               for n in names) and all(
        record["stats"].get(k) == v for k, v in stats.items())


@dataclass
class StartupTime:
    spans: list         # the closed records, as the program hands them out
    jax_seconds: dict   # JAX's own totals over every program

    def __post_init__(self):
        own = {r["id"]: r["seconds"] for r in self.spans}
        for r in self.spans:
            if r["parent"] in own:
                own[r["parent"]] -= r["seconds"]
        self.self_seconds = own

    def seconds(self, names, **stats):
        found = [self.self_seconds[r["id"]] for r in self.spans
                 if _matches(r, names, stats)]
        return sum(found) if found else None

    @property
    def top_level_s(self) -> float:
        return sum(r["seconds"] for r in self.spans if r["parent"] is None)

    def report(self) -> dict:
        """What a person reads beside the metrics (an `[info]` line)."""
        return {"spans": [dict(r, self_seconds=self.self_seconds[r["id"]])
                          for r in self.spans],
                "top_level_s": self.top_level_s,
                "jax_process_seconds": self.jax_seconds}


def compute(spans, jax_seconds) -> StartupTime:
    return StartupTime([r for r in spans if r["end"] is not None],
                       dict(jax_seconds))


def read(run):
    """The StartupTime of this process, computed once a run, or None (see
    the module docstring)."""
    if "_startup_time" not in run:
        found = _from_the_program()
        st = run["_startup_time"] = \
            None if found is None else compute(*found)
        if st is not None:
            print("[info] " + json.dumps({"setup_by_phase": st.report()}),
                  flush=True)
    return run["_startup_time"]


def phase_s(run, *names, **stats):
    st = read(run)
    return None if st is None else st.seconds(names, **stats)
