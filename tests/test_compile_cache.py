"""Persistent compile cache (ISSUE 7): keying, tiers, corruption,
eviction, and the serving/fused/ops wiring.

Fast tests use private :class:`CompileCache` instances over tmp_path —
the process-wide cache stays untouched (``cc.reset()`` restores the
env-driven default, which is OFF in the test session).  The
cross-process warm-start proof (a fresh subprocess serving with ZERO
XLA compiles) is marked slow — tier-1 runs near its wall-clock cap —
and runs in the nightly compile-cache stage.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache as cc
from mxnet_tpu import nd, serving
from mxnet_tpu.contrib import deploy
from mxnet_tpu.gluon import nn

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate_process_cache():
    """Every test leaves the process-wide cache as it found it (off,
    unless the session exported MXNET_COMPILE_CACHE_DIR)."""
    yield
    cc.reset()


@pytest.fixture
def preserve_exec_caches():
    """Snapshot/restore the SESSION-WIDE executable caches (registry
    jit/grad, fused).  Tests that clear or cap-churn them must not
    evict the warm executables every later test file in the tier-1
    session would otherwise silently recompile — that re-warm once
    cost the suite its wall-clock budget."""
    from mxnet_tpu.ops import registry
    from mxnet_tpu.optimizer import fused

    with registry._jit_lock:
        jit, grad = dict(registry._jit_cache), dict(registry._grad_cache)
    with fused._CACHE_LOCK:
        fcache = dict(fused._CACHE)
    yield
    with registry._jit_lock:
        registry._jit_cache.clear()
        registry._jit_cache.update(jit)
        registry._grad_cache.clear()
        registry._grad_cache.update(grad)
    with fused._CACHE_LOCK:
        fused._CACHE.clear()
        fused._CACHE.update(fcache)


@pytest.fixture
def artifact(tmp_path):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu", in_units=6),
                nn.Dense(4, in_units=8))
    net.initialize(ctx=mx.cpu())
    x = nd.array(np.random.RandomState(0).rand(4, 6).astype("f4"))
    art = str(tmp_path / "art")
    deploy.export_model(net, art, [x], dynamic_batch=True)
    return art


def _jit_key_and_compile(n=4, c=2.0):
    """A tiny jax program + its CacheKey + a counting compile_fn."""
    import jax
    import jax.numpy as jnp

    def f(x):
        return x * c + 1.0

    lowered = jax.jit(f).lower(jax.ShapeDtypeStruct((n,), jnp.float32))
    key = cc.cache_key("test.site", parts=("f", n, c),
                       program_text=lowered.as_text())
    calls = [0]

    def compile_fn():
        calls[0] += 1
        return lowered.compile()

    return key, compile_fn, calls


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

class TestKeys:
    def test_digest_stable_and_sensitive(self):
        k1 = cc.cache_key("s", parts=(1, "a", (2, 3)), program_text="P")
        k2 = cc.cache_key("s", parts=(1, "a", (2, 3)), program_text="P")
        assert k1.digest == k2.digest
        # every component matters
        assert cc.cache_key("s2", parts=(1, "a", (2, 3)),
                            program_text="P").digest != k1.digest
        assert cc.cache_key("s", parts=(1, "a", (2, 4)),
                            program_text="P").digest != k1.digest
        assert cc.cache_key("s", parts=(1, "a", (2, 3)),
                            program_text="Q").digest != k1.digest
        assert cc.cache_key("s", parts=(1, "a", (2, 3))).digest \
            != k1.digest

    def test_env_fingerprint_pins_versions(self):
        import jax

        fp = cc.env_fingerprint()
        assert any(jax.__version__ in p for p in fp)
        assert any(p.startswith("platform=") for p in fp)
        assert any(p.startswith("mxnet_tpu=") for p in fp)

    def test_dict_parts_canonical_order(self):
        a = cc.cache_key("s", parts=({"x": 1, "y": 2},))
        b = cc.cache_key("s", parts=({"y": 2, "x": 1},))
        assert a.digest == b.digest


# ---------------------------------------------------------------------------
# tiers
# ---------------------------------------------------------------------------

class TestTiers:
    def test_memory_tier(self, tmp_path):
        cache = cc.CompileCache(disk_dir=str(tmp_path))
        key, compile_fn, calls = _jit_key_and_compile()
        exe, origin = cache.get_or_compile("t", key, compile_fn)
        assert origin == "compiled" and calls[0] == 1
        np.testing.assert_allclose(
            np.asarray(exe(np.ones(4, np.float32))), [3, 3, 3, 3])
        exe2, origin = cache.get_or_compile("t", key, compile_fn)
        assert origin == "memory" and calls[0] == 1
        assert exe2 is exe
        assert cache.stats()["memory_hits"] == 1

    def test_disk_tier_fresh_instance(self, tmp_path):
        cache = cc.CompileCache(disk_dir=str(tmp_path))
        key, compile_fn, calls = _jit_key_and_compile()
        cache.get_or_compile("t", key, compile_fn)
        # a fresh instance = a fresh process's view of the same dir
        cache2 = cc.CompileCache(disk_dir=str(tmp_path))
        exe, origin = cache2.get_or_compile("t", key, compile_fn)
        assert origin == "disk" and calls[0] == 1  # no second compile
        np.testing.assert_allclose(
            np.asarray(exe(np.ones(4, np.float32))), [3, 3, 3, 3])
        st = cache2.stats()
        assert st["disk_hits"] == 1 and st["misses"] == 0

    def test_alias_skips_full_key(self, tmp_path):
        """An alias hit must not even BUILD the full key (that is the
        trace+lower a warm restart skips)."""
        cache = cc.CompileCache(disk_dir=str(tmp_path))
        key, compile_fn, calls = _jit_key_and_compile()
        alias = cc.cache_key("t.alias", parts=("cheap", 4))
        cache.get_or_compile("t", key, compile_fn, alias=alias)
        assert calls[0] == 1

        cache2 = cc.CompileCache(disk_dir=str(tmp_path))
        built = [0]

        def full_key():
            built[0] += 1
            return key

        exe, origin = cache2.get_or_compile("t", full_key, compile_fn,
                                            alias=alias)
        assert origin == "disk"
        assert built[0] == 0 and calls[0] == 1
        np.testing.assert_allclose(
            np.asarray(exe(np.ones(4, np.float32))), [3, 3, 3, 3])

    def test_entry_header_self_describes(self, tmp_path):
        from mxnet_tpu.compile_cache import store as ccstore

        cache = cc.CompileCache(disk_dir=str(tmp_path))
        key, compile_fn, _ = _jit_key_and_compile()
        cache.get_or_compile("t", key, compile_fn)
        blob = open(cache.disk.path(key.digest), "rb").read()
        header, payload = ccstore.decode_entry(blob, key.digest)
        assert header["tier"] in ("exec", "stablehlo")
        assert header["site"] == "t"
        assert header["digest"] == key.digest
        assert any("jax=" in e for e in header["env"])


# ---------------------------------------------------------------------------
# durability
# ---------------------------------------------------------------------------

class TestDurability:
    def test_corrupt_entry_quarantined_never_fails(self, tmp_path):
        cache = cc.CompileCache(disk_dir=str(tmp_path))
        key, compile_fn, calls = _jit_key_and_compile()
        cache.get_or_compile("t", key, compile_fn)
        p = cache.disk.path(key.digest)
        blob = open(p, "rb").read()
        open(p, "wb").write(blob[:-8] + b"CORRUPT!")  # torn tail

        cache2 = cc.CompileCache(disk_dir=str(tmp_path))
        exe, origin = cache2.get_or_compile("t", key, compile_fn)
        assert origin == "compiled" and calls[0] == 2  # fresh compile
        np.testing.assert_allclose(
            np.asarray(exe(np.ones(4, np.float32))), [3, 3, 3, 3])
        st = cache2.stats()
        assert st["disk_corrupt"] == 1 and st["misses"] == 1
        quarantined = [f for f in os.listdir(tmp_path)
                       if f.endswith(".corrupt")]
        assert len(quarantined) == 1
        # the re-store healed the entry: next instance hits again
        cache3 = cc.CompileCache(disk_dir=str(tmp_path))
        _, origin = cache3.get_or_compile("t", key, compile_fn)
        assert origin == "disk" and calls[0] == 2

    def test_wrong_digest_content_quarantined(self, tmp_path):
        """An entry whose bytes verify but belong to ANOTHER digest
        (operator copied files around) must quarantine, not serve."""
        cache = cc.CompileCache(disk_dir=str(tmp_path))
        k1, c1, _ = _jit_key_and_compile(n=4)
        k2, c2, calls2 = _jit_key_and_compile(n=8)
        cache.get_or_compile("t", k1, c1)
        os.replace(cache.disk.path(k1.digest), cache.disk.path(k2.digest))
        cache2 = cc.CompileCache(disk_dir=str(tmp_path))
        _, origin = cache2.get_or_compile("t", k2, c2)
        assert origin == "compiled" and calls2[0] == 1
        assert cache2.stats()["disk_corrupt"] == 1

    def test_tmp_files_invisible_and_swept(self, tmp_path):
        cache = cc.CompileCache(disk_dir=str(tmp_path))
        stale = tmp_path / ".tmp-99999-1"
        stale.write_bytes(b"half a write")
        os.utime(stale, (1, 1))  # ancient
        corrupt = tmp_path / ("f" * 64 + ".mxcc.corrupt")
        corrupt.write_bytes(b"quarantined long ago")
        os.utime(corrupt, (1, 1))
        key, compile_fn, _ = _jit_key_and_compile()
        # the store's post-write eviction scan doubles as the sweep:
        # crashed-writer tmp litter and aged-out quarantine files go
        cache.get_or_compile("t", key, compile_fn)
        names = [p for p, _, _ in cache.disk.entries()]
        assert not any(".tmp-" in n for n in names)
        assert not stale.exists() and not corrupt.exists()
        # explicit sweep API still works for operators
        stale2 = tmp_path / ".tmp-99999-2"
        stale2.write_bytes(b"x")
        os.utime(stale2, (1, 1))
        assert cache.disk.sweep_tmp() == 1
        assert not stale2.exists()

    def test_io_chaos_retries_transparently(self, tmp_path):
        """A transient IO fault at the chaos site costs a retry, not a
        request (the resilience conventions)."""
        from mxnet_tpu.resilience import chaos

        cache = cc.CompileCache(disk_dir=str(tmp_path))
        key, compile_fn, calls = _jit_key_and_compile()
        cache.get_or_compile("t", key, compile_fn)
        cache2 = cc.CompileCache(disk_dir=str(tmp_path))
        with chaos.inject("compile_cache.io", at=1):
            exe, origin = cache2.get_or_compile("t", key, compile_fn)
        assert origin == "disk" and calls[0] == 1
        assert chaos.stats()["compile_cache.io"]["injected"] == 1

    def test_persistent_io_failure_degrades_to_compile(self, tmp_path):
        from mxnet_tpu.resilience import chaos

        cache = cc.CompileCache(disk_dir=str(tmp_path))
        key, compile_fn, calls = _jit_key_and_compile()
        cache.get_or_compile("t", key, compile_fn)
        cache2 = cc.CompileCache(disk_dir=str(tmp_path))
        with chaos.inject("compile_cache.io", times=10_000):
            exe, origin = cache2.get_or_compile("t", key, compile_fn)
        assert origin == "compiled" and calls[0] == 2
        np.testing.assert_allclose(
            np.asarray(exe(np.ones(4, np.float32))), [3, 3, 3, 3])


# ---------------------------------------------------------------------------
# capacity
# ---------------------------------------------------------------------------

class TestCapacity:
    def test_disk_lru_eviction_under_byte_cap(self, tmp_path):
        cache = cc.CompileCache(disk_dir=str(tmp_path))
        keys = []
        for i in range(4):
            k, f, _ = _jit_key_and_compile(n=4 + i)
            cache.get_or_compile("t", k, f)
            keys.append(k)
        total = cache.disk.bytes_on_disk()
        per = total // 4
        # cap to ~2 entries and write one more: oldest get evicted
        cache.disk.cap_bytes = int(per * 2.5)
        k, f, _ = _jit_key_and_compile(n=32)
        cache.get_or_compile("t", k, f)
        assert cache.disk.bytes_on_disk() <= int(per * 2.5)
        assert cache.disk.evictions >= 2
        # the newest entry survived
        assert os.path.exists(cache.disk.path(k.digest))

    def test_memory_tier_bounded(self, tmp_path):
        cache = cc.CompileCache(disk_dir=None, mem_entries=2)
        for i in range(4):
            k, f, _ = _jit_key_and_compile(n=4 + i)
            cache.get_or_compile("t", k, f)
        st = cache.stats()
        assert st["mem_entries"] <= 2
        assert st["mem_evictions"] == 2


# ---------------------------------------------------------------------------
# env knob plumbing
# ---------------------------------------------------------------------------

class TestEnvKnobs:
    def test_off_by_default(self, monkeypatch):
        monkeypatch.delenv("MXNET_COMPILE_CACHE_DIR", raising=False)
        cc.reset()
        assert cc.get_cache() is None and not cc.enabled()
        # pass-through still compiles (lazy key thunk never invoked)
        key, compile_fn, calls = _jit_key_and_compile()
        exe, origin = cc.get_or_compile(
            "t", lambda: (_ for _ in ()).throw(AssertionError), compile_fn)
        assert origin == "compiled" and calls[0] == 1

    def test_dir_knob_enables(self, monkeypatch, tmp_path):
        monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path))
        monkeypatch.setenv("MXNET_COMPILE_CACHE_BYTES", "12345")
        cc.reset()
        cache = cc.get_cache()
        assert cache is not None
        assert cache.disk.root == str(tmp_path)
        assert cache.disk.cap_bytes == 12345

    def test_disable_kill_switch(self, monkeypatch, tmp_path):
        monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path))
        monkeypatch.setenv("MXNET_COMPILE_CACHE_DISABLE", "1")
        cc.reset()
        assert cc.get_cache() is None


# ---------------------------------------------------------------------------
# wiring: serving
# ---------------------------------------------------------------------------

class TestServingWiring:
    def test_fresh_entry_serves_without_compile_or_program(
            self, artifact, tmp_path):
        from mxnet_tpu.telemetry import instruments as ins

        cc.reset(cc.CompileCache(disk_dir=str(tmp_path / "cache")))
        x = nd.array(np.random.RandomState(1).rand(4, 6).astype("f4"))
        repo = serving.ModelRepository()
        repo.add("cold", artifact)
        out_cold = repo.get("cold").execute(4, [x.data])
        assert ins.serving_compile_total("cold", 1).value == 1

        # a second repository entry = a restart's view (its OWN entry
        # cache is empty).  It must serve from the persistent cache:
        # zero XLA compiles AND zero StableHLO deserialization.
        repo2 = serving.ModelRepository()
        repo2.add("warm", artifact)
        e2 = repo2.get("warm")
        out_warm = e2.execute(4, [x.data])
        assert ins.serving_compile_total("warm", 1).value == 0
        assert e2.served.program_loaded is False
        np.testing.assert_allclose(np.asarray(out_warm[0]),
                                   np.asarray(out_cold[0]))
        st = cc.stats()
        assert st["memory_hits"] + st["disk_hits"] >= 1

    def test_entry_cache_release_recovers_from_cache(self, artifact,
                                                     tmp_path):
        cc.reset(cc.CompileCache(disk_dir=str(tmp_path / "cache")))
        x = nd.array(np.random.RandomState(1).rand(2, 6).astype("f4"))
        repo = serving.ModelRepository()
        repo.add("m", artifact)
        e = repo.get("m")
        e.execute(2, [x.data])
        misses0 = cc.stats()["misses"]
        with e._lock:
            e._executables.clear()  # simulate eviction/rollover release
        e.execute(2, [x.data])
        assert cc.stats()["misses"] == misses0  # cache refilled it


# ---------------------------------------------------------------------------
# wiring: fused updater
# ---------------------------------------------------------------------------

class TestFusedWiring:
    def _step(self, prefix, tmp_units=6):
        from mxnet_tpu import autograd, gluon

        net = nn.Dense(4, in_units=tmp_units, prefix=prefix)
        net.initialize(ctx=mx.cpu())
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        x = nd.array(np.random.RandomState(2).rand(
            4, tmp_units).astype("f4"))
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        tr.step(4)

    def test_fused_step_from_persistent_cache(self, tmp_path,
                                               preserve_exec_caches):
        from mxnet_tpu.optimizer import fused

        cc.reset(cc.CompileCache(disk_dir=str(tmp_path / "cache")))
        # an earlier test may have cached this exact signature
        # in-process; clear so the first step populates the (fresh)
        # persistent dir
        with fused._CACHE_LOCK:
            fused._CACHE.clear()
        self._step("ccfa_")
        before = fused.compile_stats()
        # drop the in-process executable cache: the persistent tier
        # must refill it without an XLA compile
        with fused._CACHE_LOCK:
            fused._CACHE.clear()
        self._step("ccfb_")
        after = fused.compile_stats()
        assert after["count"] == before["count"]  # no new XLA compile
        assert after["cache_loads"] == before["cache_loads"] + 1

    def test_fused_lru_cap_and_eviction_counter(self, monkeypatch,
                                                tmp_path,
                                                preserve_exec_caches):
        from mxnet_tpu import optimizer as opt_mod
        from mxnet_tpu.optimizer import fused

        monkeypatch.setenv("MXNET_FUSED_CACHE_MAX", "2")
        with fused._CACHE_LOCK:
            fused._CACHE.clear()
        ev0 = fused.compile_stats()["evictions"]
        for n in (3, 5, 7, 9):  # 4 distinct signatures
            opt = opt_mod.create("sgd", learning_rate=0.1)
            up = fused.FusedUpdater(opt)
            w = [nd.array(np.ones((n, 2), "float32"))]
            g = [nd.array(np.ones((n, 2), "float32"))]
            up.update_all([0], g, w)
        st = fused.compile_stats()
        assert st["size"] <= 2
        assert st["evictions"] >= ev0 + 2


# ---------------------------------------------------------------------------
# wiring: ops registry (opt-in)
# ---------------------------------------------------------------------------

class TestOpsWiring:
    def test_registry_cache_bounded(self, monkeypatch,
                                    preserve_exec_caches):
        from mxnet_tpu.ops import registry

        monkeypatch.setenv("MXNET_OP_CACHE_MAX", "2")
        with registry._jit_lock:
            registry._jit_cache.clear()
        info0 = registry.cache_info()
        x = nd.array(np.ones((2, 2), "float32"))
        for v in (1.5, 2.5, 3.5, 4.5):  # distinct _mul_scalar attrs
            x * v
        info = registry.cache_info()
        assert info["jit_entries"] <= 2
        assert info["jit_evictions"] >= info0["jit_evictions"] + 2
        monkeypatch.setenv("MXNET_OP_CACHE_MAX", "4096")

    def test_ops_aot_opt_in_roundtrip(self, monkeypatch, tmp_path,
                                      preserve_exec_caches):
        """MXNET_COMPILE_CACHE_OPS=1: eager ops dispatch through
        persistently-cached AOT executables; results are identical and
        a fresh cache instance re-serves them from disk."""
        from mxnet_tpu.ops import registry

        cc.reset(cc.CompileCache(disk_dir=str(tmp_path / "cache")))
        monkeypatch.setenv("MXNET_COMPILE_CACHE_OPS", "1")
        registry._refresh_ops_aot()
        try:
            a = nd.array(np.random.RandomState(3).rand(
                3, 3).astype("f4"))
            b = nd.array(np.random.RandomState(4).rand(
                3, 3).astype("f4"))
            want = np.asarray(a.data) + np.asarray(b.data)
            np.testing.assert_allclose((a + b).asnumpy(), want,
                                       rtol=1e-6)
            st = cc.stats()
            assert st["misses"] >= 1
            # fresh memory tier, same dir → the op comes off disk
            cc.reset(cc.CompileCache(disk_dir=str(tmp_path / "cache")))
            registry._refresh_ops_aot()
            np.testing.assert_allclose((a + b).asnumpy(), want,
                                       rtol=1e-6)
            assert cc.stats()["disk_hits"] >= 1
            # python-scalar operands fall back to the lazy path safely
            np.testing.assert_allclose(
                (a * 2.0).asnumpy(), np.asarray(a.data) * 2.0,
                rtol=1e-6)
        finally:
            monkeypatch.setenv("MXNET_COMPILE_CACHE_OPS", "0")
            registry._refresh_ops_aot()


# ---------------------------------------------------------------------------
# cross-process warm start (the acceptance criterion) — nightly lane
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, {repo!r})
import mxnet_tpu as mx
from mxnet_tpu import compile_cache as cc, nd, serving
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.optimizer import fused
from mxnet_tpu.telemetry import instruments as ins

# serve the first request
x = nd.array(np.random.RandomState(1).rand(4, 6).astype("f4"))
repo = serving.ModelRepository()
repo.add("m", {artifact!r})
entry = repo.get("m")
out = entry.execute(4, [x.data])

# take the first fused step
net = nn.Dense(4, in_units=6, prefix="ccsub_")
net.initialize(ctx=mx.cpu())
tr = gluon.Trainer(net.collect_params(), "sgd", {{"learning_rate": 0.1}})
with autograd.record():
    loss = (net(x) ** 2).sum()
loss.backward()
tr.step(4)

print(json.dumps({{
    "serving_compiles": ins.serving_compile_total("m", 1).value,
    "fused_compiles": fused.compile_stats()["count"],
    "fused_cache_loads": fused.compile_stats()["cache_loads"],
    "program_loaded": entry.served.program_loaded,
    "cache": cc.stats(),
    "out0": float(np.asarray(out[0])[0, 0]),
}}))
"""


@pytest.mark.slow
def test_warm_subprocess_serves_and_steps_with_zero_compiles(
        artifact, tmp_path):
    """The acceptance criterion: a FRESH PROCESS with a pre-warmed
    cache dir serves its first request and takes its first fused step
    without invoking XLA compilation at either site."""
    cache_dir = str(tmp_path / "cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE_DIR=cache_dir)
    child = _CHILD.format(repo=_REPO, artifact=artifact)

    def run():
        p = subprocess.run([sys.executable, "-c", child],
                           capture_output=True, text=True, env=env,
                           timeout=300)
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        return json.loads(p.stdout.splitlines()[-1])

    cold = run()   # populates the cache (and compiles)
    assert cold["serving_compiles"] == 1
    assert cold["fused_compiles"] == 1
    warm = run()   # the warm restart under test
    assert warm["serving_compiles"] == 0
    assert warm["fused_compiles"] == 0
    assert warm["fused_cache_loads"] == 1
    assert warm["program_loaded"] is False  # StableHLO never parsed
    assert warm["cache"]["disk_hits"] >= 2
    assert warm["cache"]["misses"] == 0
    assert warm["out0"] == cold["out0"]  # identical serving output


@pytest.mark.slow
def test_warm_cache_tool_populates_for_subprocess(artifact, tmp_path):
    """tools/warm_cache.py is sufficient warmup: a process that never
    compiled anything serves from what the TOOL wrote."""
    cache_dir = str(tmp_path / "cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE_DIR=cache_dir)
    p = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "warm_cache.py"),
         "--cache-dir", cache_dir, "--artifact", artifact,
         "--buckets", "4",
         "--optimizer", "sgd", "--opt-args", "learning_rate=0.1",
         "--shapes", "4x6,4"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    report = json.loads(p.stdout.splitlines()[-1])
    assert report["serving"]["buckets_warmed"] == [4]
    assert report["stats"]["writes"] >= 2

    child = _CHILD.format(repo=_REPO, artifact=artifact)
    q = subprocess.run([sys.executable, "-c", child],
                       capture_output=True, text=True, env=env,
                       timeout=300)
    assert q.returncode == 0, q.stdout[-2000:] + q.stderr[-2000:]
    row = json.loads(q.stdout.splitlines()[-1])
    assert row["serving_compiles"] == 0
    # the tool warmed the 6x4,4 sgd shape = exactly the child's net
    assert row["fused_compiles"] == 0


class TestMxflowHardening:
    """ISSUE 8: the MX008 finding the dataflow rules surfaced in
    compile_cache/ is FIXED — the env-configured cache (and its
    DiskStore directory IO) is built OUTSIDE ``_active_lock``, so
    get_cache/reset/enabled never stall behind filesystem work."""

    def test_get_cache_builds_outside_the_active_lock(self, monkeypatch):
        from mxnet_tpu.compile_cache import core

        cc.reset(None)  # force the build path on next get_cache
        started = threading.Event()
        release = threading.Event()

        def slow_build():
            started.set()
            release.wait(5.0)
            return None

        monkeypatch.setattr(core, "_build_from_env", slow_build)
        t = threading.Thread(target=core.get_cache)
        t.start()
        try:
            assert started.wait(5.0)
            t0 = time.monotonic()
            # takes _active_lock: must NOT wait for the slow build
            cc.reset(disabled=True)
            dt = time.monotonic() - t0
            assert dt < 0.25, (
                f"_active_lock held {dt:.3f}s across the cache build")
            # the build that loses the publish race must not clobber
            # the state reset() installed
            release.set()
            t.join(5.0)
            assert cc.get_cache() is None
        finally:
            release.set()
            t.join(5.0)

    def test_concurrent_get_cache_publishes_one_instance(self, tmp_path,
                                                         monkeypatch):
        from mxnet_tpu.compile_cache import core

        cc.reset(None)
        barrier = threading.Barrier(2, timeout=5.0)

        def build():
            barrier.wait()
            return cc.CompileCache(disk_dir=str(tmp_path / "d"))

        monkeypatch.setattr(core, "_build_from_env", build)
        out = []
        threads = [threading.Thread(
            target=lambda: out.append(core.get_cache()))
            for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
        assert len(out) == 2
        # both racing builders resolve to the ONE published instance
        assert out[0] is out[1]
        assert core.get_cache() is out[0]
