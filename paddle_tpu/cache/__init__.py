"""paddle_tpu.cache — the pluggable compile cache behind both executors.

Two levels:

  L1  in-process OrderedDict of live compiled callables, keyed by the
      executor's (id(program), mutation, ...) tuple. True LRU: a hit
      moves the entry to the tail, the FLAGS_compile_cache_cap eviction
      pops the head — so a hot entry is never evicted to make room (the
      old per-executor dicts popped insertion order regardless of use).

  L2  optional persistent store (FLAGS_compile_cache_dir, store.L2Store)
      of executables serialized via jax.experimental.serialize_executable,
      keyed by a process-stable content digest (keys.stable_digest). A
      process that misses L1 but hits L2 deserializes instead of
      compiling — sub-second warm start for fleet replica spin-up,
      resilience restore, and elastic resize. Absent entry = l2_miss;
      corrupt / version-stale / undeserializable entry = l2_fallback
      (counted, silently recompiled — NEVER an exception to run()).

The executors own one CompileCache each (kind "executor" /
"parallel_executor"). Instance counters (hits/misses/evictions + the
l2_* family) always track and surface through compile_cache_info();
monitor-registry counters additionally tick when FLAGS_monitor is on
(the disabled-mode contract keeps the registry untouched otherwise).

Every miss of L1 also leaves a record of where its seconds went
(builds.py): compile_cache_info()["builds"] for one executor,
build_log() for the process. Always on; a hit of L1 leaves none.
"""

import os
import pickle
import time
from collections import OrderedDict, deque

import jax
from jax.experimental import serialize_executable as _se

from .. import flags
from . import builds, service
from .builds import build_log
from .keys import environment, program_digest, stable_digest
from .store import L2Store

__all__ = ["CompileCache", "L2Store", "build_log", "builds",
           "default_store", "environment", "place_jax_cache",
           "program_digest", "service", "stable_digest"]

flags.define(
    "compile_cache_dir", str, "",
    "Persistent compile-cache directory (the L2 behind each executor's "
    "in-memory cache): compiled step executables are serialized via JAX "
    "AOT export and re-loaded by later processes, so a restarted fleet "
    "replica or a resized elastic worker starts with zero compiles. "
    "Entries are invalidated by content digest (program, feed specs, "
    "amp/zero1/autoshard/overlap config, jax+jaxlib version, device "
    "geometry). Empty: disabled.")
flags.define(
    "compile_cache_dir_max_mb", int, 2048,
    "Size cap for FLAGS_compile_cache_dir in MiB. After every store "
    "write the directory is pruned oldest-used-first (mtime LRU) down "
    "to the cap; <= 0 leaves it unbounded.")

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_jax_cache():
    """Place JAX's own persistent compilation cache; both executors call
    this from their constructors. Where JAX_COMPILATION_CACHE_DIR is set
    (jax reads it into jax_compilation_cache_dir at import) the cache
    lives there and this sets nothing. Otherwise it is the fixed path
    <checkout>/.jax_cache (git-ignored): the directory is part of what a
    later process must find again, so it is never built from tempfile, a
    pid or the time. Returns the directory in effect."""
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def default_store():
    """L2Store at FLAGS_compile_cache_dir, or None when the flag is empty
    (re-read per call: tests and the fleet CLI flip the flag at runtime)."""
    root = flags.get("compile_cache_dir")
    return L2Store(root) if root else None


def _l2_count(which, kind, n=1):
    """Registry counter compile_cache_l2_<which>_total{cache=kind}, gated
    on monitor.enabled() (the FLAGS_monitor=0 no-registry contract)."""
    from .. import monitor

    if monitor.enabled():
        monitor.cache_l2(kind, which, n)


class CompileCache:
    """One executor's compile cache: Mapping-like L1 LRU + optional L2.

    Keeps the raw-dict surface tests and tools poke (len/iter/in/
    values/items/[]), so swapping it in for the old `_compile_cache = {}`
    is invisible to callers that only read.
    """

    def __init__(self, kind="executor"):
        self.kind = kind
        self._entries = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.l2_hits = 0
        self.l2_misses = 0
        self.l2_fallbacks = 0
        self.l2_puts = 0
        self.l2_put_bytes = 0
        # distributed compile service (FLAGS_compile_service): local-L2
        # misses satisfied by fetching a peer's blob vs. escalated to a
        # local compile (we won the single-flight lease, or no service)
        self.l2_remote_hits = 0
        self.l2_remote_misses = 0
        # this cache's newest builds (builds.py; the process's log keeps
        # them too): compile_cache_info()["builds"]
        self._builds = deque(maxlen=builds.LOG_CAP)
        builds.install()

    # -- L1 ------------------------------------------------------------
    def get(self, key):
        """Counted LRU lookup: a hit refreshes the entry's recency."""
        entry = self._entries.get(key)
        if entry is not None:
            self.hits += 1
            self._entries.move_to_end(key)
        else:
            self.misses += 1
        return entry

    def put(self, key, entry, mon=None):
        """Insert at the recency tail, evicting least-recently-USED heads
        while FLAGS_compile_cache_cap bounds the cache."""
        cap = flags.get("compile_cache_cap")
        if cap and cap > 0:
            while len(self._entries) >= cap \
                    and key not in self._entries:
                self._entries.popitem(last=False)
                self.evictions += 1
                if mon is not None and mon.monitored:
                    from .. import monitor

                    monitor.cache_evicted(self.kind)
                    if mon.extra is None:
                        mon.extra = {}
                    mon.extra["cache_evictions"] = \
                        mon.extra.get("cache_evictions", 0) + 1
        self._entries[key] = entry
        self._entries.move_to_end(key)

    # read-only dict surface (external observers; no counter side effects)
    def __len__(self):
        return len(self._entries)

    def __iter__(self):
        return iter(self._entries)

    def __contains__(self, key):
        return key in self._entries

    def __getitem__(self, key):
        return self._entries[key]

    def keys(self):
        return self._entries.keys()

    def values(self):
        return self._entries.values()

    def items(self):
        return self._entries.items()

    def clear(self):
        self._entries.clear()

    def info(self):
        """compile_cache_info() payload; "entries" key preserved (the
        serving engine diffs it across warmup)."""
        return {
            "entries": len(self._entries),
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "l2": {
                "enabled": self._l2_enabled(),
                "dir": flags.get("compile_cache_dir") or None,
                "hits": self.l2_hits,
                "misses": self.l2_misses,
                "fallbacks": self.l2_fallbacks,
                "puts": self.l2_puts,
                "put_bytes": self.l2_put_bytes,
                "remote_hits": self.l2_remote_hits,
                "remote_misses": self.l2_remote_misses,
                "service": flags.get("compile_service") or None,
            },
            "builds": [b.as_dict() for b in list(self._builds)
                       if b.t1 is not None],
        }

    # -- a miss of L1: L2, then fresh -----------------------------------
    def open_build(self, name, fingerprint, ident, parts, iters):
        """The record of the build this miss starts (builds.Build), open on
        the calling thread until its `close`: the caller stamps what it
        runs itself (`lap`), `load_or_build` its own stretches, JAX's
        events the seconds inside the jit call."""
        record = builds.open_build(self.kind, name, fingerprint, ident,
                                   parts, iters)
        self._builds.append(record)
        return record

    def load_or_build(self, key, *, content, program, build, devices,
                      record, extra=(), use_cache=True, mon=None):
        """What an executor does with a key `get` did not find: the stored
        executable of the key's content digest loaded onto `devices` and
        guarded through its first call, or `build(aot)` — the caller's
        closure, handed the export hook for
        executor_core.compile_step_fn(aot=...), None when nothing is to be
        exported; then `put`. Returns (callable, level): level "l2" for a
        loaded executable, None for a fresh one. `content` is the key's
        process-stable part (executor_core.step_key) and `extra` the
        caller's device / mesh context; `use_cache=False` builds fresh
        and touches neither level. `record`: the open build (`open_build`),
        which is told the `digest`, `l2_load` and `export` stretches."""
        digest = loaded = None
        if use_cache and self._l2_enabled():
            digest = stable_digest(
                program, content,
                extra=(("kind", self.kind),) + tuple(extra))
            record.lap("digest")
            loaded = self._l2_load(digest, devices, mon=mon)
            record.lap("l2_load")
        if loaded is not None:
            # warm start (a restarted process, a fleet replica, an elastic
            # re-join): deserialized instead of compiled
            value, level = self._guard_l2(
                loaded, lambda: build(None), mon=mon), "l2"
        else:
            value, level = build(self._aot_sink(digest, record)), None
        if use_cache:
            self.put(key, value, mon=mon)
        return value, level

    # -- L2 ------------------------------------------------------------
    def _l2_enabled(self):
        return bool(flags.get("compile_cache_dir"))

    def store(self):
        return default_store()

    def _l2_load(self, digest, devices, mon=None):
        """Deserialize one stored executable into a callable Compiled,
        loaded onto `devices` — the executor's own device assignment, in
        mesh order. Without it jax loads onto every device of the default
        backend, and a one-device step reloaded in a process that sees
        eight (or on a four-chip host) expects eight shards. None on miss
        or fallback (corrupt / version-stale / deserialize failure) —
        counted, never raised."""
        store = self.store()
        if store is None or digest is None:
            return None
        outcome, payload, _header = store.get(digest)
        if outcome == "miss":
            payload = self._remote_fetch(digest, store, mon)
            if payload is None:
                self.l2_misses += 1
                _l2_count("misses", self.kind)
                return None
        elif outcome != "hit":
            self.count_l2_fallback(mon, reason=outcome)
            return None
        devices = list(devices)
        try:
            parts = pickle.loads(payload)
            compiled = _se.deserialize_and_load(
                *parts, backend=devices[0].client,
                execution_devices=devices)
        except Exception:
            self.count_l2_fallback(mon, reason="deserialize")
            return None
        self.l2_hits += 1
        _l2_count("hits", self.kind)
        return compiled

    def _remote_fetch(self, digest, store, mon=None):
        """fetch_compiled: satisfy a local-L2 miss from the distributed
        compile service. Returns the entry's payload bytes (committed to
        the local store first, exactly as a local put would land) or
        None — None means THIS process compiles, either because it won
        the single-flight lease, the leaseholder died, or the service is
        off/unreachable."""
        if not service.enabled():
            return None
        blob = service.fetch_blob(digest, wait_s=0.0)
        if blob is None:
            if service.try_lease(digest):
                # our lease: compile here; _aot_sink publishes the blob
                self.l2_remote_misses += 1
                _l2_count("remote_misses", self.kind)
                return None
            # someone else is compiling this digest right now — park for
            # their publish instead of burning a duplicate compile
            blob = service.fetch_blob(digest, wait_s=service.WAIT_S)
        if blob is None:
            self.l2_remote_misses += 1
            _l2_count("remote_misses", self.kind)
            return None
        # commit through put_blob (framing + digest + checksum checks),
        # then re-read: the fetched entry must be exactly as trustworthy
        # as a locally written one, or we fall back to compiling
        max_mb = int(flags.get("compile_cache_dir_max_mb"))
        if not store.put_blob(
                digest, blob,
                max_bytes=max_mb * (1 << 20) if max_mb > 0 else None):
            self.count_l2_fallback(mon, reason="remote_corrupt")
            return None
        outcome, payload, _header = store.get(digest)
        if outcome != "hit" or payload is None:
            self.count_l2_fallback(mon, reason=f"remote_{outcome}")
            return None
        self.l2_remote_hits += 1
        _l2_count("remote_hits", self.kind)
        return payload

    def count_l2_fallback(self, mon=None, reason=None):
        self.l2_fallbacks += 1
        _l2_count("fallbacks", self.kind)
        if mon is not None:
            if mon.extra is None:
                mon.extra = {}
            mon.extra["cache_l2_fallback"] = reason or "fallback"

    def _aot_sink(self, digest, record, meta=None):
        """Export callback for executor_core.compile_step_fn(aot=...):
        receives the freshly AOT-compiled executable once, right after its
        first execution is set up, and serializes it into the store; the
        seconds that takes are the build record's `export`. None when L2
        is off (compile_step_fn then skips the AOT detour). Export
        failures are swallowed — a cache write must never fail the step."""
        if digest is None or not self._l2_enabled():
            return None

        def sink(compiled_exe):
            t0 = time.perf_counter()
            export(compiled_exe)
            record.add("export", time.perf_counter() - t0)

        def export(compiled_exe):
            store = self.store()
            if store is None:
                return
            try:
                payload = pickle.dumps(
                    _se.serialize(compiled_exe),
                    protocol=pickle.HIGHEST_PROTOCOL)
                max_mb = int(flags.get("compile_cache_dir_max_mb"))
                nbytes = store.put(
                    digest, payload, kind=self.kind, meta=meta,
                    max_bytes=max_mb * (1 << 20) if max_mb > 0 else None)
            except Exception:
                return
            self.l2_puts += 1
            self.l2_put_bytes += nbytes
            _l2_count("puts", self.kind)
            _l2_count("put_bytes", self.kind, nbytes)
            if service.enabled():
                # publish to the compile service: releases our
                # single-flight lease and wakes every peer parked on
                # this digest (faults swallowed inside offer_blob)
                blob = store.read_blob(digest)
                if blob is not None:
                    service.offer_blob(digest, blob)

        return sink

    def _guard_l2(self, loaded, rebuild, mon=None):
        """Wrap a deserialized executable so a latent incompatibility the
        header checks can't see (aval/sharding/device-assignment drift)
        surfaces on the FIRST call — jax validates arguments before
        dispatch (TypeError/ValueError) and the runtime rejects a shard or
        device mismatch before it executes (JaxRuntimeError), so no buffer
        has been donated yet and it is safe to rebuild fresh and retry.
        After one clean call the loaded executable is trusted unguarded."""
        box = [None]

        def call(*args):
            if box[0] is not None:
                return box[0](*args)
            try:
                out = loaded(*args)
            except (TypeError, ValueError, jax.errors.JaxRuntimeError):
                self.count_l2_fallback(mon, reason="call")
                box[0] = rebuild()
                return box[0](*args)
            box[0] = loaded
            return out

        return call
