"""Tier-1 mxlint gate (ISSUE 4): the framework must lint clean against
the committed baseline, the baseline must ratchet (new violations
fail), docs/env_vars.md must match the live knob registry, and the
lint-driven thread-safety fixes must hold under contention."""
import json
import os
import threading
import time

import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis
from mxnet_tpu.util import env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BASELINE = os.path.join(_REPO, "MXLINT_BASELINE.json")
_PKG = os.path.join(_REPO, "mxnet_tpu")


_LINT_CACHE = []


def _run_lint():
    """One full-package lint shared by every assertion in this module
    (each run costs ~4s of tier-1 budget)."""
    if not _LINT_CACHE:
        eng = analysis.LintEngine(root=_REPO)
        t0 = time.perf_counter()
        violations = eng.run([_PKG])
        _LINT_CACHE.append((eng, violations, time.perf_counter() - t0))
    return _LINT_CACHE[0]


class TestSelfLintGate:
    def test_package_lints_clean_against_baseline(self):
        eng, violations, elapsed = _run_lint()
        new, suppressed, stale = analysis.diff_baseline(
            violations, analysis.load_baseline(_BASELINE))
        assert eng.errors == [], f"unparsable files: {eng.errors}"
        assert new == [], (
            "NEW mxlint violations (fix them or — with a written "
            "justification — add to MXLINT_BASELINE.json):\n"
            + "\n".join(v.format() for v in new))
        # acceptance criterion (ISSUE 8): full-package lint incl. the
        # mxflow whole-program pass stays under 30s even with a cold
        # summary cache (warm runs are ~6s)
        assert elapsed < 30.0, f"lint took {elapsed:.1f}s (budget 30s)"

    def test_introducing_a_violation_fails_the_gate(self, tmp_path):
        bad = tmp_path / "regression.py"
        bad.write_text("_CACHE = {}\n\n"
                       "def put(k, v):\n"
                       "    _CACHE[k] = v\n")
        eng = analysis.LintEngine(root=_REPO)
        violations = eng.run([str(bad)])  # package itself is covered
                                          # by the gate test above
        new, _, _ = analysis.diff_baseline(
            violations, analysis.load_baseline(_BASELINE))
        assert [v.rule for v in new] == ["MX004"]

    def test_baseline_entries_all_have_justifications(self):
        entries = analysis.load_baseline(_BASELINE)
        assert entries, "baseline unexpectedly empty"
        bad = [e for e in entries
               if not e.get("justification", "").strip()]
        assert bad == []

    def test_no_stale_baseline_entries(self):
        _, violations, _ = _run_lint()
        _, _, stale = analysis.diff_baseline(
            violations, analysis.load_baseline(_BASELINE))
        assert stale == [], (
            "baseline entries whose violation was fixed — delete them "
            "(ratchet down):\n" + json.dumps(stale, indent=1))


class TestEnvDocsSync:
    def test_env_vars_md_matches_registry(self):
        committed = open(os.path.join(_REPO, "docs", "env_vars.md"),
                         encoding="utf-8").read()
        assert committed == env.generate_docs(), (
            "docs/env_vars.md is stale — regenerate with "
            "`python tools/mxlint.py --env-docs docs/env_vars.md`")

    def test_every_mxnet_read_site_is_declared(self):
        # the knob registry raises on undeclared names; a couple of
        # spot checks that migrated call sites resolve
        assert env.is_declared("MXNET_ENGINE_TYPE")
        assert env.is_declared("MXNET_FUSED_BUCKET_BYTES")
        with pytest.raises(mx.MXNetError):
            env.get_bool("MXNET_TOTALLY_UNKNOWN_KNOB")

    def test_numeric_bool_values_keep_working(self, monkeypatch):
        # reference knobs are int-typed booleans: MXNET_TELEMETRY=2
        # historically meant true — the registry migration must not
        # turn that into an import-time crash
        monkeypatch.setenv("MXNET_TELEMETRY", "2")
        assert env.get_bool("MXNET_TELEMETRY") is True
        monkeypatch.setenv("MXNET_TELEMETRY", "0")
        assert env.get_bool("MXNET_TELEMETRY") is False
        monkeypatch.setenv("MXNET_TELEMETRY", "banana")
        with pytest.raises(mx.MXNetError):
            env.get_bool("MXNET_TELEMETRY")

    def test_empty_string_means_unset(self, monkeypatch):
        # launchers export VAR="" as the 'use the default' spelling
        monkeypatch.setenv("MXNET_KVSTORE_TIMEOUT", "")
        assert env.get_float("MXNET_KVSTORE_TIMEOUT") is None
        monkeypatch.setenv("MXNET_FUSED_BUCKET_BYTES", "")
        assert env.get_int("MXNET_FUSED_BUCKET_BYTES") == 4 << 20

    def test_duplicate_declaration_raises(self):
        with pytest.raises(mx.MXNetError, match="already registered"):
            env.declare("MXNET_ENGINE_TYPE", int, 3, "conflict")
        # even an IDENTICAL re-declaration is rejected loudly: two call
        # sites each believing they own a knob is the drift the
        # registry exists to prevent (the second would silently shadow
        # doc/tunable edits to the first)
        with pytest.raises(mx.MXNetError, match="already registered"):
            env.declare("MXNET_USE_PALLAS", bool, True,
                        "Master switch for Pallas kernels (flash "
                        "attention, fused Conv+BN). 0 selects the XLA "
                        "fallbacks with identical semantics.")


class TestLintDrivenHardening:
    """Regression tests for the CONFIRMED MX004 findings fixed in this
    PR: module caches shared with serving/dataloader threads now take
    the double-checked-lock path."""

    def test_probe_cache_single_probe_per_key(self, monkeypatch):
        from mxnet_tpu.ops import pallas_convbn as pc

        monkeypatch.setattr(pc, "_SHAPE_OK", {})
        monkeypatch.setattr(pc.env, "get_bool",
                            lambda name, default=None: False)
        compiles = []

        class _FakeJit:
            def lower(self, *a):
                return self

            def compile(self):
                compiles.append(1)
                time.sleep(0.05)
                return self

        monkeypatch.setattr(pc.jax, "jit", lambda fn: _FakeJit())
        out = []
        threads = [threading.Thread(
            target=lambda: out.append(pc._probe_ok("k", None, ())))
            for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(compiles) == 1, "one probe compile despite the race"
        assert out == [True] * 8
        assert pc._SHAPE_OK == {("k", False): True}

    def test_deploy_namedtuple_cache_yields_one_class(self):
        from mxnet_tpu.contrib import deploy

        deploy._NT_CACHE.clear()
        got = []
        barrier = threading.Barrier(8)

        def hit():
            barrier.wait()
            got.append(deploy._namedtuple_cls("Out", ("a", "b")))

        threads = [threading.Thread(target=hit) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len({id(c) for c in got}) == 1, \
            "identity-stable class per (name, fields) key"

    def test_symbol_namespace_cache_identity(self):
        import mxnet_tpu.symbol as sym

        sym._CACHE.pop("relu", None)
        got = []
        barrier = threading.Barrier(8)

        def hit():
            barrier.wait()
            got.append(getattr(sym, "relu"))

        threads = [threading.Thread(target=hit) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # the lazily generated wrapper must resolve to ONE function
        # object no matter which thread generated it
        assert len({id(f) for f in got}) == 1

    def test_profiler_set_config_is_lock_guarded(self):
        # concurrent set_config must neither corrupt nor lose keys
        before = dict(mx.profiler._config)
        try:
            threads = [threading.Thread(
                target=mx.profiler.set_config,
                kwargs={"aggregate_stats": bool(i % 2)})
                for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert set(mx.profiler._config) == set(before)
        finally:
            mx.profiler.set_config(
                aggregate_stats=before["aggregate_stats"])


class TestDocDriftSync:
    """Cross-artifact drift (ISSUE 8, the cheap seventh pass): the
    operator-facing docs must list what actually exists — same shape
    as the env_vars.md sync gate."""

    def test_instruments_and_chaos_sites_are_documented(self):
        assert analysis.drift_findings(_REPO) == [], (
            "doc drift — every telemetry instrument belongs in "
            "docs/observability.md, every chaos site in "
            "docs/resilience.md (run `python tools/mxlint.py --drift`)")

    def test_scanners_see_the_real_surfaces(self):
        names = analysis.instrument_names(os.path.join(
            _REPO, "mxnet_tpu", "telemetry", "instruments.py"))
        assert "mx_retry_total" in names
        assert "mx_compile_cache_hit_total" in names
        assert len(names) >= 15
        sites = analysis.chaos_sites(os.path.join(_REPO, "mxnet_tpu"))
        assert {"serving.execute", "compile_cache.io",
                "dataloader.worker"} <= sites

    def test_missing_doc_row_is_reported(self, tmp_path):
        # synthetic repo: one instrument, empty docs -> one finding
        (tmp_path / "mxnet_tpu" / "telemetry").mkdir(parents=True)
        (tmp_path / "docs").mkdir()
        (tmp_path / "mxnet_tpu" / "telemetry" / "instruments.py"
         ).write_text('def x():\n    return _child("mx_shiny_total",'
                      ' "counter", "h")\n')
        (tmp_path / "docs" / "observability.md").write_text("# empty\n")
        (tmp_path / "docs" / "resilience.md").write_text("# empty\n")
        findings = analysis.drift_findings(str(tmp_path))
        assert any("mx_shiny_total" in f for f in findings)


class TestSarif:
    def test_render_sarif_shape_and_fingerprints(self, tmp_path):
        bad = tmp_path / "s.py"
        bad.write_text("_C = {}\n\ndef p(k, v):\n    _C[k] = v\n")
        eng = analysis.LintEngine(root=str(tmp_path))
        vs = eng.run([str(bad)])
        doc = analysis.render_sarif(vs)
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert {"MX004", "MX008", "MX012"} <= rule_ids
        [res] = [r for r in run["results"] if r["ruleId"] == "MX004"]
        assert res["partialFingerprints"]["mxlint/v1"] == \
            vs[0].fingerprint
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "s.py"
        assert loc["region"]["startLine"] == 4

    def test_cli_sarif_file_output(self, tmp_path):
        import subprocess
        import sys

        out = tmp_path / "lint.sarif"
        p = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "mxlint.py"),
             os.path.join(_REPO, "mxnet_tpu", "analysis"),
             "--baseline", _BASELINE, "--sarif", str(out)],
            capture_output=True, text=True, timeout=120, cwd=_REPO)
        assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
        doc = json.loads(out.read_text())
        assert doc["runs"][0]["results"] == []  # shipped tree is clean


class TestCLISmoke:
    def test_cli_exits_zero_on_shipped_tree(self):
        import subprocess
        import sys

        p = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "mxlint.py"),
             os.path.join(_REPO, "mxnet_tpu"),
             "--baseline", _BASELINE, "--json"],
            capture_output=True, text=True, timeout=120, cwd=_REPO)
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        report = json.loads(p.stdout)
        assert report["ok"] and report["counts"]["new"] == 0
        assert report["elapsed_seconds"] < 30.0

    def test_diff_mode_flags_an_untracked_violating_file(self):
        import subprocess
        import sys

        # an untracked file inside the repo is "changed vs HEAD"
        tmp = os.path.join(_REPO, "tests", "_tmp_diff_fixture.py")
        with open(tmp, "w") as f:
            f.write("_C = {}\n\ndef p(k, v):\n    _C[k] = v\n")
        try:
            p = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "tools", "mxlint.py"), tmp,
                 "--diff", "HEAD", "--json"],
                capture_output=True, text=True, timeout=60, cwd=_REPO)
            report = json.loads(p.stdout)
            assert p.returncode == 1
            assert report["new_per_rule"] == {"MX004": 1}
            # the point of --diff: a one-file lint is near-instant
            assert report["elapsed_seconds"] < 2.0
        finally:
            os.unlink(tmp)

    def test_diff_mode_clean_scope_is_instant_ok(self):
        import subprocess
        import sys

        p = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "mxlint.py"),
             os.path.join(_REPO, "docs"), "--diff", "HEAD"],
            capture_output=True, text=True, timeout=60, cwd=_REPO)
        assert p.returncode == 0, p.stdout + p.stderr
        assert "no .py files changed" in p.stdout \
            or "0 new violation(s)" in p.stdout

    def test_diff_mode_relative_scope_resolves_from_any_cwd(self,
                                                           tmp_path):
        # a repo-relative scope path must work when the CLI runs from
        # another directory (pre-commit hooks rarely cd first)
        import subprocess
        import sys

        tmp = os.path.join(_REPO, "tests", "_tmp_diff_cwd_fixture.py")
        with open(tmp, "w") as f:
            f.write("_C = {}\n\ndef p(k, v):\n    _C[k] = v\n")
        try:
            p = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "tools", "mxlint.py"), "tests",
                 "--diff", "HEAD", "--json"],
                capture_output=True, text=True, timeout=60,
                cwd=str(tmp_path))
            report = json.loads(p.stdout)
            assert p.returncode == 1, p.stdout[-500:] + p.stderr[-500:]
            # other changed files under tests/ may add findings; the
            # point is that the repo-relative scope resolved at all
            assert any(v["path"].endswith("_tmp_diff_cwd_fixture.py")
                       for v in report["new"])
        finally:
            os.unlink(tmp)


class TestIncrementalCache:
    """ISSUE 19 satellite: findings cache keyed on (content sha256,
    rules-version).  The invariant everything rests on: a warm run is
    finding-identical to a cold run — including MX006's cross-file
    duplicate detection, which replays per-file contributions instead
    of per-file findings."""

    def _tree(self, tmp_path):
        (tmp_path / "a.py").write_text(
            "import jax\n\n"
            "from mxnet_tpu.ops.registry import register_op\n\n\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return int(x) + 1\n\n\n"
            "@register_op(\"dup_op\")\n"
            "def _dup1(a):\n"
            "    return a\n")
        (tmp_path / "b.py").write_text(
            "from mxnet_tpu.ops.registry import register_op\n\n\n"
            "@register_op(\"dup_op\")\n"
            "def _dup2(a):\n"
            "    \"\"\"Doc.\"\"\"\n"
            "    return a\n")
        return str(tmp_path), str(tmp_path / "cache.json")

    def test_cold_warm_parity(self, tmp_path):
        root, cache = self._tree(tmp_path)
        cold_eng = analysis.LintEngine(root=root)
        cold = cold_eng.run([root], cache_path=cache)
        assert cold_eng.cache_misses == 2 and cold_eng.cache_hits == 0
        # the synthetic tree must exercise a "file" rule, a per-file
        # MX006 finding, AND the cross-file MX006 dup
        assert {v.rule for v in cold} >= {"MX001", "MX006"}
        assert any("already registered" in v.message for v in cold)
        warm_eng = analysis.LintEngine(root=root)
        warm = warm_eng.run([root], cache_path=cache)
        assert warm_eng.cache_hits == 2 and warm_eng.cache_misses == 0
        assert warm == cold

    def test_edit_invalidates_only_that_file(self, tmp_path):
        root, cache = self._tree(tmp_path)
        analysis.LintEngine(root=root).run([root], cache_path=cache)
        (tmp_path / "b.py").write_text(
            "from mxnet_tpu.ops.registry import register_op\n\n\n"
            "@register_op(\"other_op\")\n"
            "def _dup2(a):\n"
            "    \"\"\"Doc.\"\"\"\n"
            "    return a\n")
        eng = analysis.LintEngine(root=root)
        vs = eng.run([root], cache_path=cache)
        assert eng.cache_hits == 1 and eng.cache_misses == 1
        assert not any("already registered" in v.message for v in vs)

    def test_rules_version_change_invalidates_everything(self, tmp_path):
        root, cache = self._tree(tmp_path)
        analysis.LintEngine(root=root).run([root], cache_path=cache)
        with open(cache) as f:
            doc = json.load(f)
        doc["rules_version"] = "0" * 64
        with open(cache, "w") as f:
            json.dump(doc, f)
        eng = analysis.LintEngine(root=root)
        eng.run([root], cache_path=cache)
        assert eng.cache_misses == 2 and eng.cache_hits == 0

    def test_corrupt_cache_is_a_cold_run_not_an_error(self, tmp_path):
        root, cache = self._tree(tmp_path)
        with open(cache, "w") as f:
            f.write("{not json")
        eng = analysis.LintEngine(root=root)
        vs = eng.run([root], cache_path=cache)
        assert eng.cache_misses == 2 and vs
        with open(cache) as f:
            assert json.load(f)["version"] == 1  # rewritten valid

    def test_no_cache_path_writes_nothing(self, tmp_path):
        root, cache = self._tree(tmp_path)
        analysis.LintEngine(root=root).run([root])
        assert not os.path.exists(cache)

    def test_narrower_enable_entry_does_not_serve_wider_run(self,
                                                            tmp_path):
        # an entry written by --enable=MX001 lacks the other cacheable
        # rules' findings; a full run must treat it as a miss, never
        # silently drop findings
        root, cache = self._tree(tmp_path)
        analysis.LintEngine(root=root, enable=["MX001"]).run(
            [root], cache_path=cache)
        eng = analysis.LintEngine(root=root)
        vs = eng.run([root], cache_path=cache)
        assert eng.cache_misses == 2
        assert any(v.rule == "MX006" for v in vs)

    def test_cli_cache_flags(self, tmp_path):
        import subprocess
        import sys

        root, _ = self._tree(tmp_path)
        cache = str(tmp_path / "cli_cache.json")
        cli = [sys.executable, os.path.join(_REPO, "tools", "mxlint.py"),
               root, "--json", "--cache-file", cache]
        runs = []
        for extra in ([], [], ["--no-cache"]):
            p = subprocess.run(cli + extra, capture_output=True,
                               text=True, timeout=60)
            runs.append(json.loads(p.stdout))
        cold, warm, off = runs
        assert cold["cache"] == {"enabled": True, "hits": 0, "misses": 2}
        assert warm["cache"] == {"enabled": True, "hits": 2, "misses": 0}
        assert off["cache"]["enabled"] is False
        assert cold["new"] == warm["new"] == off["new"]


class TestLintDocsSync:
    """tools/gen_lint_docs.py: the rule catalogue table in
    docs/static_analysis.md is generated from RULE_REGISTRY and must
    not drift (the registry-then-docs contract gen_metric_docs keeps
    for metrics and --env-docs keeps for knobs)."""

    def _mod(self):
        import importlib.util
        path = os.path.join(_REPO, "tools", "gen_lint_docs.py")
        spec = importlib.util.spec_from_file_location("gen_lint_docs",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_catalog_in_sync(self):
        mod = self._mod()
        ok, table = mod.apply_block(
            os.path.join(_REPO, "docs", "static_analysis.md"),
            write=False)
        assert ok, ("lint rule catalogue out of sync — run "
                    "`python tools/gen_lint_docs.py --write`")
        # every registered rule has a row
        for rid in analysis.RULE_REGISTRY:
            assert f"| {rid} |" in table

    def test_check_mode_detects_drift(self, tmp_path):
        mod = self._mod()
        doc = tmp_path / "doc.md"
        doc.write_text("x\n" + mod._BEGIN + "\nstale\n" + mod._END
                       + "\ny\n")
        ok, _ = mod.apply_block(str(doc), write=False)
        assert not ok
        ok, _ = mod.apply_block(str(doc), write=True)
        ok2, _ = mod.apply_block(str(doc), write=False)
        assert ok2

    def test_missing_markers_is_an_error(self, tmp_path):
        mod = self._mod()
        doc = tmp_path / "doc.md"
        doc.write_text("no markers here\n")
        with pytest.raises(ValueError):
            mod.apply_block(str(doc), write=False)
