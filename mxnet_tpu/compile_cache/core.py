"""The two-tier compile cache behind ``get_or_compile``.

Tier 1 is an in-process dict keyed by the full digest: a site whose own
(bounded) executable cache just evicted an entry gets it back here for
the price of a lower + digest, never an XLA compile.  Tier 2 is the
content-addressed :class:`~mxnet_tpu.compile_cache.store.DiskStore`,
shared across processes: a fresh process (deploy, preemption restart,
autoscale-up) loads yesterday's executables instead of paying the
compile storm.

Entry tiers (self-described in the entry header):

  * ``exec`` — the serialized compiled executable
    (``jax.experimental.serialize_executable``).  A hit deserializes
    and runs: **no XLA compilation at all**.
  * ``stablehlo`` — the lowered module text, persisted when the
    backend cannot serialize the executable.  A hit proves the program
    is byte-identical to a known-good build and re-``compile()``\\ s the
    caller's in-process lowering (trace+lower were already spent
    producing the digest); the compile still runs, so call sites count
    it as a real build.

``get_or_compile`` returns ``(executable, origin)`` with origin one of
``"memory"`` / ``"disk"`` / ``"compiled"`` — call sites use it to keep
their compile counters honest (a disk hit must not look like a compile,
and vice versa) and to hand :func:`mxsan.record_compile` its cache
provenance.
"""
from __future__ import annotations

import itertools
import pickle
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from ..analysis import sanitizer as _mxsan
from ..telemetry import instruments as _ins
from ..util import env as _env
from .key import CacheKey, env_fingerprint
from .store import DiskStore

__all__ = ["CompileCache", "get_cache", "get_or_compile", "stats",
           "reset", "enabled"]

_TICKS = itertools.count(1)


class _MemEntry:
    """``touch`` is the EXEC-tier disk digest this entry's payload
    lives under — what a memory hit must mtime-refresh so byte-cap
    eviction sees the use.  For an alias-keyed entry that is the alias
    TARGET, not the (tiny) alias file itself."""

    __slots__ = ("fn", "tick", "touch")

    def __init__(self, fn, touch=None):
        self.fn = fn
        self.tick = next(_TICKS)
        self.touch = touch


def _encode_executable(compiled: Any,
                       program_text: Optional[str]) -> Optional[Tuple[str, bytes]]:
    """(tier, payload) for one compiled executable, or None when
    nothing persistable exists (serialization unsupported AND no
    program text to fall back to)."""
    try:
        from jax.experimental import serialize_executable as _se

        payload, in_tree, out_tree = _se.serialize(compiled)
        # the device assignment rides with the payload: jax's loader
        # defaults execution_devices to EVERY device of the backend, so
        # a one-device program reloaded on a four-chip host would
        # demand four shards
        device_ids = [d.id for d in
                      compiled._executable._unloaded_executable.device_list]
        return "exec", pickle.dumps(
            {"payload": payload, "in_tree": in_tree,
             "out_tree": out_tree, "device_ids": device_ids},
            protocol=pickle.HIGHEST_PROTOCOL)
    except Exception:  # noqa: BLE001 — backend/runtime may not support it
        if program_text is not None:
            return "stablehlo", program_text.encode()
        return None


def _decode_executable(payload: bytes) -> Any:
    """Rehydrate an ``exec``-tier payload into a callable executable."""
    from jax.experimental import serialize_executable as _se

    import jax

    d = pickle.loads(payload)
    by_id = {dev.id: dev for dev in jax.devices()}
    return _se.deserialize_and_load(
        d["payload"], d["in_tree"], d["out_tree"],
        execution_devices=[by_id[i] for i in d["device_ids"]])


class CompileCache:
    """One memory+disk cache.  The process normally holds a single
    instance (:func:`get_cache`); tests construct private ones."""

    def __init__(self, disk_dir: Optional[str] = None,
                 cap_bytes: int = 0, mem_entries: int = 256):
        self._lock = threading.Lock()
        # mxsan: all memory-tier accesses hold self._lock (digest
        # lookups are rare — once per site-cache miss, not per step)
        self._mem: Dict[str, _MemEntry] = _mxsan.track(
            {}, "compile_cache._mem")
        self.mem_entries = int(mem_entries)
        self.disk = DiskStore(disk_dir, cap_bytes) if disk_dir else None
        # process-local stats: cheap to assert in tests, mirrored to
        # telemetry for operations
        self._stats = {"memory_hits": 0, "disk_hits": 0,
                       "stablehlo_hits": 0, "misses": 0, "writes": 0,
                       "write_errors": 0, "mem_evictions": 0,
                       "decode_failures": 0}

    # ---- the one public verb -----------------------------------------

    def get_or_compile(self, site: str, key, compile_fn: Callable[[], Any],
                       alias: Optional[CacheKey] = None) -> Tuple[Any, str]:
        """The executable for ``key``: memory tier, then disk, then
        ``compile_fn()`` (storing the result).  Returns
        ``(executable, origin)``; origin ``"compiled"`` means an XLA
        compilation actually ran in this call.

        ``key`` may be a :class:`CacheKey` or a zero-arg callable
        returning one — pass a callable when building the full key is
        itself expensive (it digests the lowered program text, so it
        needs trace+lower).  ``alias`` is a CHEAP secondary key (no
        program text: artifact fingerprint + bucket + avals) stored as
        a tiny index entry pointing at the full digest.  An alias hit
        on a warm process therefore skips trace+lower entirely — the
        difference between "restart compiles nothing" and "restart
        still re-traces every program to find out it compiled
        nothing"."""
        adig = alias.digest if alias is not None else None
        if adig is not None:
            hit = self._mem_hit(site, adig)
            if hit is not None:
                return hit, "memory"
            if self.disk is not None:
                got = self._load_alias(site, adig)
                if got is not None:
                    exe, target = got
                    self._mem_put(adig, exe, touch=target)
                    return exe, "disk"

        if callable(key) and not isinstance(key, CacheKey):
            key = key()
        digest = key.digest
        hit = self._mem_hit(site, digest)
        if hit is not None:
            if adig is not None:
                self._mem_put(adig, hit, touch=digest)
            return hit, "memory"

        if self.disk is not None:
            got = self._load_disk(site, digest)
            if got is not None:
                exe, origin = got
                if exe is not None:
                    self._mem_put(digest, exe, touch=digest)
                    if adig is not None:
                        self._mem_put(adig, exe, touch=digest)
                        self._store_alias(adig, digest)
                    return exe, origin
                # stablehlo tier: the program is known-good but the
                # executable wasn't persistable — compile the caller's
                # in-process lowering (counted as a real build)
                compiled = compile_fn()
                self._mem_put(digest, compiled, touch=digest)
                return compiled, "compiled"

        with self._lock:
            self._stats["misses"] += 1
        _ins.compile_cache_miss_total(site).inc()
        # compile provenance (telemetry.mxtriage): every miss records
        # WHICH signature component changed vs the nearest prior
        # compile at this site — the recompile-storm diagnosis layer
        # (record_miss never raises)
        from ..telemetry.mxtriage import provenance as _prov

        _prov.record_miss(site, key)
        compiled = compile_fn()
        self._mem_put(digest, compiled, touch=digest)
        if adig is not None:
            self._mem_put(adig, compiled, touch=digest)
        if self.disk is not None:
            stored = self._store_disk(site, key, digest, compiled)
            if stored and adig is not None:
                self._store_alias(adig, digest)
        return compiled, "compiled"

    def _mem_hit(self, site: str, digest: str):
        with self._lock:
            ent = self._mem.get(digest)
            if ent is not None:
                ent.tick = next(_TICKS)
                self._stats["memory_hits"] += 1
        if ent is None:
            return None
        _ins.compile_cache_hit_total(site, "memory").inc()
        if self.disk is not None and ent.touch is not None:
            # a memory-tier hit is still USE of the disk entry: refresh
            # its mtime so byte-cap eviction (LRU by mtime) does not
            # drop the hottest executables first just because their
            # consumers stopped touching the disk
            self.disk.touch(ent.touch)
        return ent.fn

    def _load_alias(self, site: str, adig: str):
        """Follow an alias index entry to its exec-tier target;
        ``(executable, target_digest)`` on a hit, None on any miss
        along the way (the caller falls through to the full path,
        which re-creates both entries)."""
        t0 = time.perf_counter()
        got = self.disk.load(adig)
        if got is None:
            return None
        header, payload = got
        if header["tier"] != "alias":
            return None
        try:
            target = payload.decode("ascii")
        except UnicodeDecodeError:
            self.disk.quarantine(adig)
            return None
        got = self.disk.load(target)
        if got is None or got[0]["tier"] != "exec":
            return None
        try:
            exe = _decode_executable(got[1])
        except Exception:  # noqa: BLE001 — incompatibility, not corruption
            with self._lock:
                self._stats["decode_failures"] += 1
            return None
        dt = time.perf_counter() - t0
        with self._lock:
            self._stats["disk_hits"] += 1
        _ins.compile_cache_hit_total(site, "exec").inc()
        _ins.compile_cache_load_seconds().observe(dt)
        return exe, target

    def _store_alias(self, adig: str, digest: str) -> None:
        fp = env_fingerprint()
        try:
            self.disk.store(adig, {"tier": "alias", "site": "alias",
                                   "env": list(fp),
                                   "created": time.time()},
                            digest.encode("ascii"))
        except Exception:  # noqa: BLE001 — index is an optimization
            with self._lock:
                self._stats["write_errors"] += 1

    # ---- tiers --------------------------------------------------------

    def _mem_put(self, digest: str, fn: Any, touch: Optional[str] = None) -> None:
        with self._lock:
            self._mem[digest] = _MemEntry(fn, touch)
            while len(self._mem) > self.mem_entries:
                oldest = min(self._mem.items(),
                             key=lambda kv: kv[1].tick)[0]
                if oldest == digest:
                    break  # never evict what we just inserted
                del self._mem[oldest]
                self._stats["mem_evictions"] += 1
                _ins.compile_cache_evict_total("memory").inc()

    def _load_disk(self, site: str, digest: str):
        """None = miss.  ``(executable, "disk")`` for an exec-tier hit;
        ``(None, "stablehlo")`` tells the caller to compile its own
        lowering (the hit is still counted — the entry verified)."""
        t0 = time.perf_counter()
        got = self.disk.load(digest)
        if got is None:
            return None
        header, payload = got
        if header["tier"] == "exec":
            try:
                exe = _decode_executable(payload)
            except Exception:  # noqa: BLE001 — stale pickle, runtime drift
                # the bytes VERIFIED but this runtime rejected them —
                # that is an incompatibility (fingerprint gap), not
                # corruption.  Count a miss and compile fresh; do NOT
                # quarantine: on a shared cache dir that would let one
                # incompatible host destroy entries that are perfectly
                # valid for their writers.
                with self._lock:
                    self._stats["decode_failures"] += 1
                return None
            dt = time.perf_counter() - t0
            with self._lock:
                self._stats["disk_hits"] += 1
            _ins.compile_cache_hit_total(site, "exec").inc()
            _ins.compile_cache_load_seconds().observe(dt)
            return exe, "disk"
        with self._lock:
            self._stats["stablehlo_hits"] += 1
        _ins.compile_cache_hit_total(site, "stablehlo").inc()
        return None, "stablehlo"

    def _store_disk(self, site: str, key: CacheKey, digest: str,
                    compiled: Any) -> bool:
        """Persist a fresh build; True when an exec-tier entry landed
        (aliases only point at exec entries).  Best-effort: a full disk
        or IO error costs durability, never the request — but it is
        counted (``write_errors``) so a silently-cold cache is
        diagnosable."""
        enc = _encode_executable(compiled, key.program_text)
        if enc is None:
            return False
        tier, payload = enc
        fp = env_fingerprint()
        header = {"tier": tier, "site": site,
                  "env": list(fp),
                  "created": time.time()}
        try:
            self.disk.store(digest, header, payload)
        except Exception:  # noqa: BLE001 — durability is best-effort
            with self._lock:
                self._stats["write_errors"] += 1
            return False
        with self._lock:
            self._stats["writes"] += 1
        evicted, live_bytes = self.disk.evict()
        if evicted:
            _ins.compile_cache_evict_total("disk").inc(evicted)
        _ins.compile_cache_bytes().set(live_bytes)
        return tier == "exec"

    # ---- introspection ------------------------------------------------

    def stats(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self._stats)
        if self.disk is not None:
            out["disk_evictions"] = self.disk.evictions
            out["disk_corrupt"] = self.disk.corrupt
            out["bytes_on_disk"] = self.disk.bytes_on_disk()
        out["mem_entries"] = len(self._mem)
        return out


# ---------------------------------------------------------------------------
# the process-wide instance (env-configured, lazily built)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[CompileCache] = None
_DISABLED_SENTINEL = object()
_active_lock = threading.Lock()


def _build_from_env() -> Optional[CompileCache]:
    if _env.get_bool("MXNET_COMPILE_CACHE_DISABLE"):
        return None
    d = _env.get_str("MXNET_COMPILE_CACHE_DIR")
    if not d:
        return None
    return CompileCache(disk_dir=d,
                        cap_bytes=_env.get_int("MXNET_COMPILE_CACHE_BYTES"))


def get_cache() -> Optional[CompileCache]:
    """The env-configured process cache, or None when the persistent
    cache is off (no ``MXNET_COMPILE_CACHE_DIR``, or explicitly
    disabled).  Off is the default: call sites keep their own
    in-process caches either way."""
    global _ACTIVE
    a = _ACTIVE
    if a is None:
        # build OUTSIDE the lock (mxflow MX008: DiskStore creation
        # does directory IO, and every get_cache/reset/enabled call
        # contends on _active_lock — op dispatch holds its own lock
        # while calling in here).  Racing builders produce equivalent
        # instances; the first to publish wins, the loser's instance
        # holds no resources (makedirs is idempotent, no open fds).
        built = _build_from_env()
        with _active_lock:
            if _ACTIVE is None:
                _ACTIVE = built if built is not None \
                    else _DISABLED_SENTINEL
            a = _ACTIVE
    return None if a is _DISABLED_SENTINEL else a


def reset(cache: Optional[CompileCache] = None,
          disabled: bool = False) -> None:
    """Swap the process cache (tests; :mod:`tools.warm_cache`).  With
    no arguments the env knobs are re-read on the next
    :func:`get_cache`."""
    global _ACTIVE
    with _active_lock:
        _ACTIVE = _DISABLED_SENTINEL if disabled else cache


def enabled() -> bool:
    return get_cache() is not None


def get_or_compile(site: str, key, compile_fn: Callable[[], Any],
                   alias: Optional[CacheKey] = None) -> Tuple[Any, str]:
    """Module-level convenience over the process cache.  With the cache
    off this is exactly ``(compile_fn(), "compiled")`` — zero overhead,
    zero behavior change (the production default until a cache dir is
    configured).  ``key`` may be a CacheKey or a lazy thunk; ``alias``
    is the cheap secondary key (see CompileCache.get_or_compile)."""
    cc = get_cache()
    if cc is None:
        if callable(key) and not isinstance(key, CacheKey):
            key = None  # never built: the thunk exists for cache keying only
        return compile_fn(), "compiled"
    return cc.get_or_compile(site, key, compile_fn, alias=alias)


def stats() -> Dict[str, int]:
    """Process-cache stats ({} when off) — what the warm-start tests
    and ``tools/warm_cache.py`` report."""
    cc = get_cache()
    return cc.stats() if cc is not None else {}
