"""mxtune (ISSUE 16): goodput-optimal knob autotuning.

Covers the registry-side pieces (Tunable metadata, env-overlay
precedence, unknown-env hygiene), the search space, the
successive-halving searcher (pruning, crash containment, pinned
default), the config store (round-trip, corrupt-entry quarantine), the
mxprof tuned-config stamp, the MXNET_PREFETCH_DEPTH DataLoader knob,
and — in the slow lane — the subprocess proof that a fresh process
with a populated store boots already-tuned.
"""
import importlib.util
import json
import os
import random
import subprocess
import sys
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autotune
from mxnet_tpu.util import env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_overlay():
    """Every test starts and ends with no tuned overlay installed."""
    env.clear_overlay()
    yield
    env.clear_overlay()


# ---------------------------------------------------------------------------
# knob-registry hygiene (satellite 1)
# ---------------------------------------------------------------------------

class TestRegistryHygiene:
    def test_duplicate_registration_raises_loudly(self):
        with pytest.raises(mx.MXNetError, match="already registered"):
            env.declare("MXNET_PREFETCH_DEPTH", int, None, "dupe")

    def test_unknown_env_warns_once_with_did_you_mean(self, monkeypatch):
        monkeypatch.setenv("MXNET_PREFTCH_DEPTH", "4")  # typo'd knob
        monkeypatch.setattr(env, "_warned_unknown_env", False)
        with pytest.warns(RuntimeWarning,
                          match="did you mean MXNET_PREFETCH_DEPTH"):
            env.resolved()
        # once per process: the second resolved() is silent
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            env.resolved()

    def test_harness_control_vars_are_exempt(self, monkeypatch):
        monkeypatch.setenv("MXNET_NIGHTLY", "1")
        monkeypatch.setenv("MXNET_TEST_SEED", "0")
        monkeypatch.setattr(env, "_warned_unknown_env", False)
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            env.resolved()

    def test_tunable_metadata_rides_the_registry(self):
        names = {k.name for k in env.tunables()}
        assert "MXNET_PREFETCH_DEPTH" in names
        assert "MXNET_FUSED_BUCKET_BYTES" in names
        k = next(k for k in env.tunables()
                 if k.name == "MXNET_FUSED_BUCKET_BYTES")
        assert k.tunable.scale == "log"
        assert k.tunable.lo < k.default < k.tunable.hi


# ---------------------------------------------------------------------------
# env-overlay precedence (tentpole + satellite tests)
# ---------------------------------------------------------------------------

class TestOverlayPrecedence:
    def test_explicit_env_beats_overlay_beats_default(self, monkeypatch):
        assert env.get_int("MXNET_ZERO_MIN_SIZE") == 2048  # default
        info = env.apply_overlay({"MXNET_ZERO_MIN_SIZE": 4096})
        assert info["applied"] == ["MXNET_ZERO_MIN_SIZE"]
        assert env.get_int("MXNET_ZERO_MIN_SIZE") == 4096   # overlay
        monkeypatch.setenv("MXNET_ZERO_MIN_SIZE", "1024")
        assert env.get_int("MXNET_ZERO_MIN_SIZE") == 1024   # env wins

    def test_env_set_before_apply_is_shadowed(self, monkeypatch):
        monkeypatch.setenv("MXNET_ZERO_MIN_SIZE", "1024")
        info = env.apply_overlay({"MXNET_ZERO_MIN_SIZE": 4096})
        assert info["shadowed"] == ["MXNET_ZERO_MIN_SIZE"]
        assert env.get_int("MXNET_ZERO_MIN_SIZE") == 1024

    def test_empty_string_env_means_unset_so_overlay_applies(
            self, monkeypatch):
        # launchers export VAR="" as 'use the default' — the overlay IS
        # the default then
        monkeypatch.setenv("MXNET_ZERO_MIN_SIZE", "")
        env.apply_overlay({"MXNET_ZERO_MIN_SIZE": 4096})
        assert env.get_int("MXNET_ZERO_MIN_SIZE") == 4096

    def test_unregistered_names_ignored_not_fatal(self):
        info = env.apply_overlay({"MXNET_GONE_KNOB": 7,
                                  "MXNET_ZERO_MIN_SIZE": 4096})
        assert info["ignored"] == ["MXNET_GONE_KNOB"]
        assert info["applied"] == ["MXNET_ZERO_MIN_SIZE"]

    def test_bool_and_float_values_convert_like_env(self):
        env.apply_overlay({"MXNET_SPMD": True,
                           "MXNET_RETRY_BASE_MS": 75.5})
        assert env.get_bool("MXNET_SPMD") is True
        assert env.get_float("MXNET_RETRY_BASE_MS") == 75.5

    def test_clear_overlay_restores_defaults(self):
        env.apply_overlay({"MXNET_ZERO_MIN_SIZE": 4096})
        env.clear_overlay()
        assert env.get_int("MXNET_ZERO_MIN_SIZE") == 2048
        assert env.overlay_info() is None

    def test_fingerprint_stable_across_application_order(self):
        cfg = {"MXNET_ZERO_MIN_SIZE": 4096,
               "MXNET_RETRY_BASE_MS": 75.0,
               "MXNET_FUSED_CACHE_MAX": 128}
        env.apply_overlay(cfg)
        fp_once = env.fingerprint()
        env.clear_overlay()
        for name in reversed(sorted(cfg)):  # one at a time, reversed
            env.apply_overlay({name: cfg[name]})
        assert env.fingerprint() == fp_once
        # and the config's own identity is order-independent too
        assert autotune.config_fingerprint(cfg) == \
            autotune.config_fingerprint(
                dict(reversed(list(cfg.items()))))


# ---------------------------------------------------------------------------
# search space
# ---------------------------------------------------------------------------

class TestSpace:
    def test_sample_respects_declared_bounds(self):
        dims = autotune.dimensions()
        rng = random.Random(0)
        for _ in range(20):
            cfg = autotune.sample(rng, dims)
            for d in dims:
                v = cfg[d.name]
                if d.tunable.choices is not None:
                    assert v in d.tunable.choices
                    continue
                assert d.tunable.lo <= v <= d.tunable.hi
                assert isinstance(v, int) if d.typ is int else True

    def test_neighbor_moves_one_dimension_within_bounds(self):
        dims = autotune.dimensions()
        rng = random.Random(1)
        base = autotune.sample(rng, dims)
        for _ in range(20):
            nxt = autotune.neighbor(rng, base, dims)
            changed = [n for n in nxt if nxt[n] != base.get(n)]
            assert len(changed) == 1
            d = next(d for d in dims if d.name == changed[0])
            if d.tunable.choices is not None:
                assert nxt[changed[0]] in d.tunable.choices
            else:
                assert d.tunable.lo <= nxt[changed[0]] <= d.tunable.hi

    def test_dimensions_subset_orders_and_validates(self):
        dims = autotune.dimensions(["MXNET_PREFETCH_DEPTH",
                                    "MXNET_FUSED_BUCKET_BYTES"])
        assert [d.name for d in dims] == ["MXNET_PREFETCH_DEPTH",
                                          "MXNET_FUSED_BUCKET_BYTES"]
        with pytest.raises(mx.MXNetError, match="not a tunable"):
            autotune.dimensions(["MXNET_ENGINE_TYPE"])

    def test_priority_from_suspects_filters_to_tunables(self):
        suspects = [
            {"kind": "phase", "name": "grad-allreduce", "score": 9},
            {"kind": "knob", "name": "MXNET_FUSED_BUCKET_BYTES",
             "score": 5},
            {"kind": "knob", "name": "MXNET_ENGINE_TYPE", "score": 5},
            {"kind": "knob", "name": "MXNET_FUSED_BUCKET_BYTES",
             "score": 4},  # dupe, rank preserved
            {"kind": "knob", "name": "MXNET_PREFETCH_DEPTH",
             "score": 3},
        ]
        assert autotune.priority_from_suspects(suspects) == \
            ["MXNET_FUSED_BUCKET_BYTES", "MXNET_PREFETCH_DEPTH"]


# ---------------------------------------------------------------------------
# successive halving
# ---------------------------------------------------------------------------

def _bucket_dims():
    return autotune.dimensions(["MXNET_FUSED_BUCKET_BYTES"])


class TestSearch:
    def test_halving_prunes_seeded_slow_config(self):
        """A runner where small bucket-bytes wins: the sweep must find
        a config beating the 4MiB default, and must have pruned arms
        along the way."""
        def runner(config, budget):
            v = config.get("MXNET_FUSED_BUCKET_BYTES", 4 << 20)
            return {"objective": 1e7 / v, "ok": True}

        rep = autotune.successive_halving(
            runner, _bucket_dims(), rng=random.Random(3),
            n_initial=8, rungs=3)
        assert rep["ok"]
        assert rep["best_objective"] >= rep["default_objective"]
        assert rep["delta"] >= 0
        assert rep["pruned"] > 0
        assert len(rep["trajectory"]) == 3
        # budgets grow per rung
        assert rep["trajectory"][1]["budget"] == \
            2 * rep["trajectory"][0]["budget"]
        assert rep["best_config"]["MXNET_FUSED_BUCKET_BYTES"] < 4 << 20

    def test_crashed_trial_counted_not_fatal(self):
        def crasher(config, budget):
            if config:  # every non-default arm dies
                raise RuntimeError("simulated OOM")
            return {"objective": 0.9}

        rep = autotune.successive_halving(
            crasher, _bucket_dims(), rng=random.Random(4),
            n_initial=6, rungs=2)
        assert rep["ok"]
        assert rep["crashed"] > 0
        assert rep["best_config"] == {}  # default survives and wins
        assert rep["best_objective"] == 0.9

    def test_timeout_style_none_result_is_pruned(self):
        def timeouter(config, budget):
            return None if config else {"objective": 0.5}

        rep = autotune.successive_halving(
            timeouter, _bucket_dims(), rng=random.Random(5),
            n_initial=4, rungs=2)
        assert rep["ok"] and rep["best_config"] == {}
        assert rep["crashed"] == rep["trials"] - 2  # default runs twice

    def test_default_always_remeasured_at_final_rung(self):
        calls = []

        def runner(config, budget):
            calls.append((not config, budget))
            # default is deliberately WORST: it must still be measured
            # at every rung despite ranking last
            return {"objective": 0.1 if not config else 0.9}

        rep = autotune.successive_halving(
            runner, _bucket_dims(), rng=random.Random(6),
            n_initial=6, rungs=3)
        budgets = sorted(b for is_default, b in calls if is_default)
        assert len(budgets) == 3  # one default measurement per rung
        assert rep["default_objective"] == 0.1
        assert rep["delta"] == pytest.approx(0.8)

    def test_tiebreak_orders_equal_objectives(self):
        def runner(config, budget):
            mfu = 0.9 if config else 0.1
            return {"objective": 0.5, "tiebreak": (mfu,)}

        rep = autotune.successive_halving(
            runner, _bucket_dims(), rng=random.Random(7),
            n_initial=4, rungs=2)
        assert rep["best_config"] != {}
        assert rep["delta"] == 0.0  # ties the default on the objective


# ---------------------------------------------------------------------------
# config store
# ---------------------------------------------------------------------------

class TestStore:
    def _key(self, scenario="mlp_train", version="v1", platform="cpu"):
        return autotune.entry_key(scenario=scenario, mesh=[8],
                                  device_kind="host",
                                  framework_version=version,
                                  platform=platform)

    def test_round_trip(self, tmp_path):
        s = autotune.ConfigStore(str(tmp_path))
        cfg = {"MXNET_ZERO_MIN_SIZE": 4096, "MXNET_PREFETCH_DEPTH": 6}
        s.put(self._key(), cfg, 0.93, meta={"quick": True})
        e = s.get(self._key())
        assert e["config"] == cfg
        assert e["objective"] == 0.93
        assert e["config_fingerprint"] == \
            autotune.config_fingerprint(cfg)
        assert s.stats["hits"] == 1 and s.stats["corrupt"] == 0

    def test_miss_on_absent_key(self, tmp_path):
        s = autotune.ConfigStore(str(tmp_path))
        assert s.get(self._key()) is None
        assert s.stats["misses"] == 1

    def test_corrupt_entry_quarantined_and_missed(self, tmp_path):
        s = autotune.ConfigStore(str(tmp_path))
        path = s.put(self._key(), {"MXNET_ZERO_MIN_SIZE": 4096}, 0.9)
        with open(path, "wb") as f:
            f.write(b'{"not": "an entry"}')
        assert s.get(self._key()) is None  # a miss, never an error
        assert s.stats["corrupt"] == 1
        assert os.path.exists(path + ".corrupt")
        assert not os.path.exists(path)
        # a tampered config (fingerprint mismatch) is also corrupt
        path2 = s.put(self._key("other"), {"MXNET_ZERO_MIN_SIZE": 1}, 1)
        blob = json.load(open(path2))
        blob["config"]["MXNET_ZERO_MIN_SIZE"] = 9999
        with open(path2, "w") as f:
            json.dump(blob, f)
        assert s.get(self._key("other")) is None
        assert s.stats["corrupt"] == 2

    def test_best_for_startup_matching(self, tmp_path):
        s = autotune.ConfigStore(str(tmp_path))
        s.put(self._key(version="OLD"), {"MXNET_ZERO_MIN_SIZE": 1}, 1)
        s.put(self._key(platform="tpu"), {"MXNET_ZERO_MIN_SIZE": 2}, 1)
        s.put(self._key(platform="cpu"), {"MXNET_ZERO_MIN_SIZE": 3}, 1)
        # version must match exactly; this platform's entry preferred
        e = s.best_for_startup(framework_version="v1", platform="cpu")
        assert e["config"] == {"MXNET_ZERO_MIN_SIZE": 3}
        # a pinned scenario that matches nothing: None, never a guess
        assert s.best_for_startup(scenario="resnet",
                                  framework_version="v1") is None
        assert s.best_for_startup(framework_version="v9") is None


# ---------------------------------------------------------------------------
# mxprof stamp + prefetch knob
# ---------------------------------------------------------------------------

class TestTunedConfigStamp:
    def test_dump_carries_tuned_fingerprint_and_overlay_knobs(self):
        from mxnet_tpu.telemetry import mxprof

        mxprof.enable()
        try:
            self._dump_carries_the_stamp(mxprof)
        finally:
            # the sink is the process's: left attached, `tracing.active()`
            # stays true for every test this worker runs afterwards
            env.clear_overlay()
            mxprof.disable()

    @staticmethod
    def _dump_carries_the_stamp(mxprof):
        cfg = {"MXNET_ZERO_MIN_SIZE": 4096}
        env.apply_overlay(cfg, fingerprint=autotune.config_fingerprint(
            cfg), source="test-store")
        d = mxprof.snapshot(live_hbm=False, include_records=False)
        assert d["tuned_config"]["fingerprint"] == \
            autotune.config_fingerprint(cfg)
        assert d["tuned_config"]["source"] == "test-store"
        assert d["tuned_config"]["applied"] == ["MXNET_ZERO_MIN_SIZE"]
        # the overlaid knob rides the knobs dict (attribution sees the
        # tuned VALUE, not just the fingerprint)
        assert d["knobs"]["MXNET_ZERO_MIN_SIZE"] == 4096
        env.clear_overlay()
        d2 = mxprof.snapshot(live_hbm=False, include_records=False)
        assert "tuned_config" not in d2


class TestPrefetchKnob:
    def test_default_preserved_without_knob(self):
        from mxnet_tpu.gluon.data import DataLoader

        ds = [np.zeros(2, np.float32)] * 8
        dl = DataLoader(ds, batch_size=2, num_workers=3)
        assert dl._prefetch == 6  # 2 * num_workers, the dynamic default
        assert DataLoader(ds, batch_size=2)._prefetch == 0

    def test_knob_plumbs_both_pools(self, monkeypatch):
        from mxnet_tpu.gluon.data import DataLoader

        monkeypatch.setenv("MXNET_PREFETCH_DEPTH", "5")
        ds = [np.zeros(2, np.float32)] * 8
        for pool in ("thread", "process"):
            dl = DataLoader(ds, batch_size=2, num_workers=2,
                            worker_pool=pool)
            assert dl._prefetch == 5, pool

    def test_explicit_argument_beats_knob(self, monkeypatch):
        from mxnet_tpu.gluon.data import DataLoader

        monkeypatch.setenv("MXNET_PREFETCH_DEPTH", "5")
        ds = [np.zeros(2, np.float32)] * 8
        dl = DataLoader(ds, batch_size=2, num_workers=2, prefetch=1)
        assert dl._prefetch == 1

    def test_overlay_feeds_knob_and_loader_still_works(self):
        from mxnet_tpu.gluon.data import DataLoader

        env.apply_overlay({"MXNET_PREFETCH_DEPTH": 3})
        ds = [np.full(2, i, np.float32) for i in range(8)]
        dl = DataLoader(ds, batch_size=2, num_workers=2,
                        worker_pool="thread")
        assert dl._prefetch == 3
        batches = list(dl)
        assert len(batches) == 4  # tuned depth changes no semantics


# ---------------------------------------------------------------------------
# CLI plumbing (fast: no sweep subprocesses)
# ---------------------------------------------------------------------------

def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "autotune_cli_under_test",
        os.path.join(_REPO, "tools", "autotune.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCliPlumbing:
    def test_from_suspects_reads_artifact_array(self, tmp_path):
        cli = _load_cli()
        rep = {"ok": False, "suspects": [
            {"kind": "phase", "name": "forward", "score": 9},
            {"kind": "knob", "name": "MXNET_PREFETCH_DEPTH",
             "score": 5},
        ]}
        p = tmp_path / "PERF_COMPARE.json"
        p.write_text(json.dumps(rep))
        logs = []
        assert cli._priority_from_file(str(p), logs.append) == \
            ["MXNET_PREFETCH_DEPTH"]

    def test_from_suspects_without_tunables_falls_back(self, tmp_path):
        cli = _load_cli()
        p = tmp_path / "PERF_COMPARE.json"
        p.write_text(json.dumps({"ok": True, "suspects": []}))
        logs = []
        assert cli._priority_from_file(str(p), logs.append) is None
        assert any("suspects" in m for m in logs)

    def test_unknown_scenario_usage_error(self, capsys):
        cli = _load_cli()
        assert cli.main(["--scenarios", "nope"]) == 2


# ---------------------------------------------------------------------------
# slow lane: subprocess proofs (nightly)
# ---------------------------------------------------------------------------

_BOOT_SNIPPET = r"""
import json
import mxnet_tpu as mx
from mxnet_tpu.telemetry import mxprof
from mxnet_tpu.util import env
d = mxprof.snapshot(live_hbm=False, include_records=False)
print(json.dumps({
    "tuned_config": d.get("tuned_config"),
    "prefetch": env.get_int("MXNET_PREFETCH_DEPTH"),
    "zero_min": env.get_int("MXNET_ZERO_MIN_SIZE"),
}))
"""


def _boot_env(store_dir, **extra):
    """A child env with ZERO manual MXNET_* knob settings: only the
    store pointer and the mxprof dump switch survive."""
    child = {k: v for k, v in os.environ.items()
             if not k.startswith("MXNET_")}
    child["JAX_PLATFORMS"] = "cpu"
    child["MXNET_AUTOTUNE_DIR"] = str(store_dir)
    child["MXNET_MXPROF"] = "1"
    child.update(extra)
    return child


@pytest.mark.slow
class TestBootTuned:
    def _populate(self, tmp_path, cfg):
        store = autotune.ConfigStore(str(tmp_path))
        key = autotune.entry_key(scenario="mlp_train", mesh=[1],
                                 device_kind="",
                                 framework_version=mx.__version__,
                                 platform="cpu")
        store.put(key, cfg, 0.95)
        return autotune.config_fingerprint(cfg)

    def test_fresh_process_boots_with_tuned_overlay(self, tmp_path):
        """The acceptance proof: a fresh process + a populated store +
        zero manual knob env = tuned overlay applied, fingerprint
        visible in its mxprof dump."""
        cfg = {"MXNET_PREFETCH_DEPTH": 6, "MXNET_ZERO_MIN_SIZE": 4096}
        fp = self._populate(tmp_path, cfg)
        p = subprocess.run([sys.executable, "-c", _BOOT_SNIPPET],
                           capture_output=True, text=True, timeout=180,
                           env=_boot_env(tmp_path), cwd=_REPO)
        assert p.returncode == 0, p.stderr[-2000:]
        got = json.loads(p.stdout.strip().splitlines()[-1])
        assert got["tuned_config"]["fingerprint"] == fp
        assert sorted(got["tuned_config"]["applied"]) == sorted(cfg)
        assert got["prefetch"] == 6
        assert got["zero_min"] == 4096

    def test_explicit_env_shadows_stored_winner(self, tmp_path):
        self._populate(tmp_path, {"MXNET_PREFETCH_DEPTH": 6,
                                  "MXNET_ZERO_MIN_SIZE": 4096})
        p = subprocess.run(
            [sys.executable, "-c", _BOOT_SNIPPET],
            capture_output=True, text=True, timeout=180,
            env=_boot_env(tmp_path, MXNET_PREFETCH_DEPTH="9"),
            cwd=_REPO)
        assert p.returncode == 0, p.stderr[-2000:]
        got = json.loads(p.stdout.strip().splitlines()[-1])
        assert got["prefetch"] == 9          # operator's explicit env
        assert got["zero_min"] == 4096       # overlay fills the rest
        assert got["tuned_config"]["shadowed"] == \
            ["MXNET_PREFETCH_DEPTH"]

    def test_autotune_off_boots_on_defaults(self, tmp_path):
        self._populate(tmp_path, {"MXNET_ZERO_MIN_SIZE": 4096})
        p = subprocess.run(
            [sys.executable, "-c", _BOOT_SNIPPET],
            capture_output=True, text=True, timeout=180,
            env=_boot_env(tmp_path, MXNET_AUTOTUNE="0"), cwd=_REPO)
        assert p.returncode == 0, p.stderr[-2000:]
        got = json.loads(p.stdout.strip().splitlines()[-1])
        assert got["tuned_config"] is None
        assert got["zero_min"] == 2048


@pytest.mark.slow
class TestCliSweep:
    def test_quick_sweep_emits_gated_artifact_and_persists(
            self, tmp_path):
        out = tmp_path / "AUTOTUNE.json"
        store = tmp_path / "store"
        p = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools",
                                          "autotune.py"),
             "--quick", "--scenarios", "io_bound",
             "--store-dir", str(store), "--out", str(out)],
            capture_output=True, text=True, timeout=560, cwd=_REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
        rep = json.load(open(out))
        assert rep["gate_ok"] is True
        row = rep["scenarios"]["io_bound"]
        assert row["ok"] and row["delta"] >= 0
        assert row["trajectory"] and row["trials"] >= 4
        assert "MXNET_PREFETCH_DEPTH" in row["dims"]
        # the winner is on disk and startup-matchable
        s = autotune.ConfigStore(str(store))
        e = s.best_for_startup(framework_version=mx.__version__,
                               platform="cpu")
        assert e is not None and e["config"] == row["best_config"]
