"""Step-level monitor: phase records, compile-cache visibility, journal.

The executors call exactly three things on the hot path:

    m = monitor.enabled()
    mon = monitor.step_begin("executor", monitored=m) \
        if m or trace.enabled() else None
    ...
    mon.lap("dispatch")                     # guarded by `mon is not None`
    ...
    monitor.step_end(mon, iters=K, datapipe=pipe)

A StepRecord serves two readers, each behind its own flag: under
FLAGS_monitor step_end folds it into the process registry (counters/
gauges/histograms), captures it as last_step(), and appends one JSONL
line when FLAGS_monitor_journal names a path; under FLAGS_trace it is
replayed as a `<kind>.step` span with one child per phase interval.
With both flags off no record is made: two flag reads per run(), no
`perf_counter` call, no allocation, no registry mutation, no journal I/O
(asserted by tests/test_monitor.py and tests/test_trace.py). FLAGS_trace
alone leaves the registry untouched.

Phases TILE the step: lap(name) closes the stretch since the previous
lap (or the step's start) under `name`, so the children of a step span
neither overlap nor leave the step's own Python between them unnamed.

Compile-cache visibility: executors mark every cache lookup
(mark_cache), and on a miss hand compile_probe() to
executor_core.compile_step_fn — the probe lowers the jitted step once,
immediately before its first execution (inputs are still alive there;
after the call donated buffers are deleted), and records the HLO cost
analysis (FLOPs + bytes accessed) per cache-key fingerprint. bench.py
turns those FLOPs into MFU (see mfu.py). The compile's wall time per
fingerprint is the wall of the build's record (cache/builds.py, always
on), whose phases step_end replays under the step span's `compile`.
"""

import threading
import time

from .. import flags
from .journal import JournalWriter
from .registry import MetricsRegistry
from .skew import replica_skew

__all__ = ["StepRecord", "enabled", "registry", "exposition", "reset",
           "step_begin", "step_end", "last_step", "compile_info",
           "record_compile", "compile_probe", "fingerprint_of",
           "cache_evicted", "cache_l2", "steps_done", "restore_steps"]

flags.define(
    "monitor_hlo_cost", bool, False,
    "On every compile-cache miss, lower the step once more and record the "
    "HLO cost analysis (FLOPs + bytes accessed) per program fingerprint — "
    "the model-FLOPs source for MFU accounting (bench.py). Off by "
    "default: the extra lowering roughly doubles trace time per compile, "
    "and on a TPU, where only a compiled executable can be analysed, it "
    "costs one more compile of the step.")
flags.define(
    "monitor_replica_skew", bool, False,
    "Measure per-replica step-completion times on the ParallelExecutor "
    "mesh each step (max/median skew, slowest replica). Fences the "
    "dispatch queue per step — a straggler-hunting mode, not a "
    "production default.")

_registry = MetricsRegistry()
_lock = threading.Lock()
_state = {
    "steps": 0,          # process-wide step index
    "last": None,        # last completed step record (dict)
    "journal": None,     # open JournalWriter
    "journal_path": None,
    "compile_info": {},  # fingerprint -> {wall_s, flops, bytes_accessed}
}


def enabled():
    """THE per-step flag check: everything else is gated on its result."""
    return bool(flags.get("monitor"))


_trace_mod = [None]


def _trace():
    """Lazy paddle_tpu.trace handle (trace imports monitor; importing it
    at module top would be circular)."""
    if _trace_mod[0] is None:
        from .. import trace

        _trace_mod[0] = trace
    return _trace_mod[0]


def registry():
    return _registry


def exposition():
    """Prometheus-style text exposition of the process registry."""
    return _registry.exposition()


def reset():
    """Fresh telemetry session: drop metrics, step records, compile info,
    and close any open journal (tests / long-lived processes)."""
    with _lock:
        _state["steps"] = 0
        _state["last"] = None
        _state["compile_info"] = {}
        w, _state["journal"], _state["journal_path"] = \
            _state["journal"], None, None
    if w is not None:
        w.close()
    _registry.reset()


class StepRecord:
    """Accumulates one step's phases; built only when FLAGS_monitor or
    FLAGS_trace is on. `monitored` says whether the registry, journal
    and last_step() may be touched (FLAGS_monitor); a record made for
    tracing alone only carries the intervals step_end replays as spans."""

    __slots__ = ("kind", "t0", "phases", "cache", "cache_level",
                 "fingerprint", "extra", "intervals", "monitored", "t_lap",
                 "lowered", "build")

    def __init__(self, kind, monitored=True):
        self.kind = kind
        self.monitored = bool(monitored)
        self.t0 = self.t_lap = time.perf_counter()  # t_lap: last lap's stamp
        self.phases = {}        # name -> seconds
        self.cache = None       # "hit" | "miss"
        self.cache_level = None  # "l1" | "l2" on a hit (l2 = warm start)
        self.fingerprint = None
        self.lowered = {}       # see mark_cache
        self.build = None       # the cache.builds.Build of a step that was
        #                         built or loaded in this run()
        self.extra = None    # journal-only extras
        self.intervals = []  # (name, t0, t1) per occurrence — the phase
        #                      boundaries step_end replays as trace spans

    def lap(self, name):
        """Close the stretch since the previous lap (the step's start for
        the first) as one interval of phase `name`; returns its seconds.
        Consecutive laps share their boundary stamp, which is what makes
        the phase children of a step span tile it."""
        t1 = time.perf_counter()
        t0, self.t_lap = self.t_lap, t1
        self.phases[name] = self.phases.get(name, 0.0) + t1 - t0
        self.intervals.append((name, t0, t1))
        return t1 - t0

    def mark_cache(self, hit, fingerprint=None, level=None, lowered=None):
        """level: "l1" (in-process) or "l2" (deserialized from the
        persistent store) on a hit. A warm-started process therefore
        reports compile_cache_misses == 0 — the contract bench.py and
        green_gate assert against FLAGS_compile_cache_dir.
        lowered: {counter: how many ops (or op pairs) of this step's
        program are lowered through that path}, from
        `executor_core.lowered_counts`: `fused_bn_global_pool` (always),
        `moe_ffn_grouped`, `grouped_matmul_kernel`, `grouped_mlp_epilogues`,
        `flash_attention`, `flash_attention_bwd` (where there are any). The
        step span carries the numbers; the registry counts them once per
        program prepared (compiled, or loaded from the persistent store)."""
        self.cache = "hit" if hit else "miss"
        self.cache_level = level if hit else None
        self.fingerprint = fingerprint
        self.lowered = dict(lowered or {})
        if not self.monitored:
            return
        if self.cache_level != "l1":
            for name, n in self.lowered.items():
                _registry.counter(
                    name, help=f"ops (or op pairs) lowered through the "
                               f"{name} path, over the programs prepared",
                    cache=self.kind).inc(n)
        _registry.counter(
            "compile_cache_hits_total" if hit else
            "compile_cache_misses_total",
            help="executor compile-cache lookups",
            cache=self.kind).inc()


def step_begin(kind="executor", monitored=True):
    """One step's record; callers gate on `enabled() or trace.enabled()`
    themselves (and pass the first as `monitored`), so the disabled path
    stays two flag reads."""
    return StepRecord(kind, monitored)


def fingerprint_of(cache_key):
    """Short stable-within-process id of a compile-cache key (joins the
    journal's cache lines with compile_info entries)."""
    return format(hash(cache_key) & 0xFFFFFFFF, "08x")


def record_compile(fingerprint, wall_s=None, flops=None,
                   bytes_accessed=None):
    """Fold one compile's wall time / HLO cost into compile_info and the
    registry (per-fingerprint gauges)."""
    with _lock:
        info = _state["compile_info"].setdefault(str(fingerprint), {})
        if wall_s is not None:
            info["wall_s"] = float(wall_s)
        if flops is not None:
            info["flops"] = float(flops)
        if bytes_accessed is not None:
            info["bytes_accessed"] = float(bytes_accessed)
    if wall_s is not None:
        _registry.gauge("compile_wall_seconds",
                        help="XLA compile wall time per program fingerprint",
                        fingerprint=str(fingerprint)).set(wall_s)
    if flops is not None:
        _registry.gauge("hlo_flops",
                        help="HLO cost analysis: FLOPs per dispatch",
                        fingerprint=str(fingerprint)).set(flops)
    if bytes_accessed is not None:
        _registry.gauge("hlo_bytes_accessed",
                        help="HLO cost analysis: bytes accessed per dispatch",
                        fingerprint=str(fingerprint)).set(bytes_accessed)


def compile_info():
    """{fingerprint: {wall_s, flops, bytes_accessed}} snapshot."""
    with _lock:
        return {k: dict(v) for k, v in _state["compile_info"].items()}


def compile_probe(fingerprint):
    """Probe for executor_core.compile_step_fn: lower the jitted step once
    (before its first execution — donated inputs are still alive) and
    record the HLO cost analysis under `fingerprint`."""

    def probe(jitted, args):
        lowered = jitted.lower(*args)
        ca = lowered.cost_analysis()
        if ca is None:
            # the TPU client (PJRT C API, libtpu 0.0.34) analyses compiled
            # executables only: Lowered.cost_analysis() is None there
            ca = lowered.compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if not isinstance(ca, dict):
            return
        record_compile(
            fingerprint,
            flops=float(ca.get("flops", 0.0) or 0.0),
            bytes_accessed=float(ca.get("bytes accessed", 0.0) or 0.0))

    return probe


def cache_evicted(kind="executor"):
    """Count one compile-cache eviction (FLAGS_compile_cache_cap)."""
    _registry.counter("compile_cache_evictions_total",
                      help="compile-cache entries evicted by the cap",
                      cache=kind).inc()


_L2_HELP = {
    "hits": "persistent compile-cache loads (warm starts)",
    "misses": "persistent compile-cache lookups with no entry",
    "fallbacks": "corrupt/stale/unloadable persistent entries "
                 "recompiled over",
    "puts": "executables serialized into the persistent store",
    "put_bytes": "bytes written to the persistent store",
}


def cache_l2(kind, which, n=1):
    """Count one persistent (L2) compile-cache event:
    compile_cache_l2_<which>_total{cache=kind}. Callers (paddle_tpu.cache)
    gate on enabled() so FLAGS_monitor=0 keeps the registry untouched."""
    _registry.counter(
        f"compile_cache_l2_{which}_total",
        help=_L2_HELP.get(which, "persistent compile-cache events"),
        cache=kind).inc(n)


def _journal_writer():
    path = flags.get("monitor_journal")
    if not path:
        return None
    with _lock:
        if _state["journal_path"] != path:
            old = _state["journal"]
            if old is not None:
                old.close()
            _state["journal"] = JournalWriter(path)
            _state["journal_path"] = path
        return _state["journal"]


def step_end(rec, iters=None, datapipe=None, replica_ms=None,
             replica_ids=None):
    """Close one StepRecord: registry metrics, last_step capture, journal.

    datapipe: the DataPipe the step pulled from (its per-step stage-stat
    deltas merge into the record); replica_ms/replica_ids: per-replica
    completion stamps from skew.measure_replica_ms."""
    if rec is None:
        return None
    total_ms = (time.perf_counter() - rec.t0) * 1000.0
    record = _monitor_step(rec, total_ms, iters, datapipe, replica_ms,
                           replica_ids) if rec.monitored else None
    # retroactive trace emission: the step and its phase boundaries are
    # already measured, so the flight recorder gets them for the price of
    # the dicts
    tr = _trace()
    if tr.enabled():
        attrs = {}
        if iters is not None:
            attrs["iters"] = iters
        if rec.cache is not None:
            attrs["cache"] = rec.cache
            attrs["fingerprint"] = rec.fingerprint
            if rec.cache_level is not None:
                attrs["cache_level"] = rec.cache_level
        attrs.update(rec.lowered)
        ctx = tr.record(f"{rec.kind}.step", rec.t0,
                        rec.t0 + total_ms / 1000.0, kind="step",
                        attrs=attrs)
        # a step built or loaded in this run(): the build's phases go under
        # its `compile` (or `cache_load`) stretches, the one before the
        # call first
        stretches = iter((rec.build.BEFORE_CALL, rec.build.IN_CALL)) \
            if rec.build is not None else None
        for name, p0, p1 in rec.intervals:
            phase = tr.record(name, p0, p1, kind="phase", parent=ctx)
            if stretches is not None and name in ("compile", "cache_load"):
                _replay_build(tr, rec.build, name, p0, p1, phase,
                              next(stretches, ()))
    return record


def _replay_build(tr, build, name, p0, p1, parent, phases):
    """One `compile` (or `cache_load`) stretch of a step span, tiled by the
    build's phases (cache.builds) as its children: `compile.verify`,
    `compile.trace`, `compile.lower`, `compile.backend`, ...,
    `compile.self`. As the laps tile the step: the stretch before the call
    holds what prepare_step stamped, the call what JAX reported, each in
    the record's order from the stretch's start, and `self` is what is
    left of the stretch."""
    attrs = {"fingerprint": build.fingerprint,
             "persistent_hit": build.persistent_hit}
    t = p0
    for phase in phases:
        t1 = min(p1, t + build.phases[phase])
        if t1 > t:
            tr.record(f"{name}.{phase}", t, t1, kind="phase", parent=parent,
                      attrs=attrs)
            t = t1
    if p1 > t:
        tr.record(f"{name}.self", t, p1, kind="phase", parent=parent,
                  attrs=attrs)


def _monitor_step(rec, total_ms, iters, datapipe, replica_ms, replica_ids):
    """step_end's FLAGS_monitor half: registry, last_step, journal."""
    _registry.counter("steps_total", help="executor steps run",
                      kind=rec.kind).inc()
    _registry.histogram("step_ms", help="wall time per executor step",
                        kind=rec.kind).observe(total_ms)
    _registry.gauge("last_step_ms", help="wall time of the last step",
                    kind=rec.kind).set(total_ms)
    phases_ms = {}
    for name, s in rec.phases.items():
        ms = s * 1000.0
        phases_ms[name] = round(ms, 6)
        _registry.histogram("step_phase_ms",
                            help="per-phase wall time within a step",
                            kind=rec.kind, phase=name).observe(ms)
        _registry.gauge("last_phase_ms",
                        help="per-phase wall time of the last step",
                        kind=rec.kind, phase=name).set(ms)

    with _lock:
        _state["steps"] += 1
        step_idx = _state["steps"]
    record = {
        "ts": time.time(),
        "step": step_idx,
        "kind": rec.kind,
        "iters": iters,
        "total_ms": round(total_ms, 6),
        "phases_ms": phases_ms,
    }
    if rec.cache is not None:
        record["cache"] = rec.cache
        record["fingerprint"] = rec.fingerprint
        if rec.cache_level is not None:
            record["cache_level"] = rec.cache_level
    if rec.extra:
        record.update(rec.extra)

    if datapipe is not None:
        try:
            delta = (datapipe.stats_delta()
                     if hasattr(datapipe, "stats_delta")
                     else datapipe.stats())
        except Exception:
            delta = None
        if delta:
            record["datapipe"] = delta
        wire = getattr(datapipe, "wire_spec", None)
        if wire is not None and hasattr(wire, "describe"):
            record["wire"] = wire.describe()

    if replica_ms:
        sk = replica_skew(replica_ms, ids=replica_ids)
        record["replica_ms"] = [round(t, 6) for t in replica_ms]
        if replica_ids is not None:
            record["replica_ids"] = list(replica_ids)
        record["skew"] = sk
        for i, t in enumerate(replica_ms):
            rid = replica_ids[i] if replica_ids is not None else i
            _registry.gauge("replica_step_ms",
                            help="per-replica step completion time",
                            replica=str(rid)).set(t)
        if sk["max_over_median"] is not None:
            _registry.gauge("replica_skew_max_over_median",
                            help="straggler signal: max/median "
                                 "per-replica step time").set(
                sk["max_over_median"])

    with _lock:
        _state["last"] = record
    writer = _journal_writer()
    if writer is not None:
        writer.write(record)
    return record


def last_step():
    """The most recent completed step record (dict), or None."""
    with _lock:
        rec = _state["last"]
        return dict(rec) if rec is not None else None


def steps_done():
    """Process-wide completed-step count (rides checkpoint manifests)."""
    with _lock:
        return _state["steps"]


def restore_steps(n):
    """Rewind/advance the step counter to a checkpoint's value, so journal
    step indices stay monotonic across a restore."""
    with _lock:
        _state["steps"] = int(n)
