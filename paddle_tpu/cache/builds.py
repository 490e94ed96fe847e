"""The build log: where the seconds of every compiled-step build went.

A miss of an executor's first cache level opens one `Build`
(`CompileCache.open_build`, from `executor.prepare_step`, before the
program is verified) and `executor.finish_step` closes it after the first
call of the step, whether the step was built fresh or loaded from the
persistent store. Always on: a build happens a handful of times a process
and costs seconds, the record a dozen `perf_counter` reads. A hit of the
first level opens nothing and reads nothing here.

The stretches the program runs itself are stamped where they run (`lap`:
`verify`, `digest`, `l2_load`; `add`: `export`). The seconds inside the
jit call come from JAX's own duration events: ONE `jax.monitoring`
listener, registered once a process when the first `CompileCache` is made,
files an event under the build that is open on its thread and returns at
once when none is. A call of a cached executable fires no event, so in a
steady state the listener is never entered (`listener_entries` counts).
An event stamps its end on `perf_counter` as it arrives and its start as
that less its seconds: the record's clock, which JAX's own time-span
events (`time.time`) are not on.

    trace    /jax/core/compile/jaxpr_trace_duration of the step's OWN jit
             (`step`, `wired`, `health_step`, `multi`): the stretch in
             which `run_ops` lowers every Fluid op. The trace events of
             OTHER jits that started inside it (a Pallas kernel's
             `jax.jit(inline=True)` wrapper) are counted under
             `nested_traces`; they lie inside `trace` and are not added
    lower    .../jaxpr_to_mlir_module_duration of `jit(<name>)`
    backend  .../backend_compile_duration of `jit(<name>)`: an XLA compile
             or a load from JAX's persistent cache (`persistent_hit`)
    self     the wall less every named phase: key building, closures,
             scope reads, other jits' compiles, the first call's enqueue

An event of a step's wrap that arrives with NO build open (JAX traced or
compiled again inside a first-level hit: a committed-ness, a sharding, an
aval the key does not hold) is logged as a record of its own with
`cause: "retrace"` and no fingerprint.
"""

import collections
import threading
import time

__all__ = ["Build", "PHASES", "build_log", "install", "listener_entries",
           "open_build", "open_builds", "reset"]

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
# fires only where JAX's persistent cache served the request, just before
# that request's backend event
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_PHASE_OF = {TRACE: "trace", LOWER: "lower", BACKEND: "backend"}

# in the order monitor.step_end replays them as `compile.*` spans: what
# runs before the jit call, what runs inside it, and the rest
BEFORE_CALL = ("verify", "digest", "l2_load")
IN_CALL = ("trace", "lower", "backend", "export")
PHASES = BEFORE_CALL + IN_CALL + ("self",)
# the names compile_step_fn's jit can carry: executor_core.build_step_fn,
# WireSpec.wrap_step, HealthPlan.wrap_step, build_multi_step_fn
WRAPS = ("step", "wired", "health_step", "multi")
_WRAP_OF = dict([(w, w) for w in WRAPS] + [(f"jit({w})", w) for w in WRAPS])

LOG_CAP = 256
NESTED_KEPT = 5

_log = collections.deque(maxlen=LOG_CAP)
_lock = threading.Lock()
_open = {}       # thread ident -> the build open on that thread
_retraced = {}   # thread ident -> that thread's last retrace record
_served = set()  # thread idents whose next backend event is a cache load
_installed = [False]
_entries = [0]


class Build:
    """One build, open from `prepare_step`'s miss to `finish_step`."""

    BEFORE_CALL, IN_CALL = BEFORE_CALL, IN_CALL
    __slots__ = ("fingerprint", "kind", "name", "iters", "t0", "t1",
                 "level", "persistent_hit", "phases", "nested", "key_diff",
                 "cause", "ident", "parts", "_t_lap", "_pending")

    def __init__(self, kind, name, fingerprint=None, ident=None, parts=None,
                 iters=None, cause=None):
        self.kind = kind
        self.name = name       # the jitted wrap's: one of WRAPS
        self.fingerprint = fingerprint
        self.ident, self.parts = ident, parts   # what key_diff compares
        self.iters = iters
        self.cause = cause     # None | "retrace" | "failed"
        self.level = None
        self.persistent_hit = False
        self.phases = dict.fromkeys(PHASES, 0.0)
        self.nested = {}       # fun_name -> [count, seconds], inside trace
        self.key_diff = []
        self.t1 = None
        self._pending = []     # (fun_name, start, seconds) of other traces
        self.t0 = self._t_lap = time.perf_counter()

    def lap(self, name):
        """The stretch since the last lap (the opening for the first) was
        phase `name`."""
        t = time.perf_counter()
        self.phases[name] += t - self._t_lap
        self._t_lap = t

    def add(self, name, seconds):
        """`seconds` of phase `name`, timed by whoever did the work."""
        self.phases[name] += seconds

    @property
    def wall(self):
        return (self.t1 if self.t1 is not None
                else time.perf_counter()) - self.t0

    def _event(self, phase, fun_name, seconds, served):
        """One of JAX's three duration events, arrived on this build's
        thread while it is open. served: a backend request that JAX's
        persistent cache answered."""
        end = time.perf_counter()
        if _WRAP_OF.get(fun_name) != self.name:
            if phase == "trace":
                self._pending.append((fun_name, end - seconds, seconds))
            return
        self.phases[phase] += seconds
        if phase == "trace":
            start = end - seconds
            for fun, began, secs in self._pending:
                if began >= start:
                    slot = self.nested.setdefault(fun, [0, 0.0])
                    slot[0] += 1
                    slot[1] += secs
            self._pending = []
        elif phase == "backend":
            self.persistent_hit = served

    def close(self, cause=None):
        """Stamp the end, give `self` what no phase claimed, file the
        record. Idempotent."""
        if self.t1 is not None:
            return
        self.t1 = time.perf_counter()
        if cause is not None:
            self.cause = cause
        named = sum(s for n, s in self.phases.items() if n != "self")
        self.phases["self"] = max(0.0, self.t1 - self.t0 - named)
        self._pending = []
        tid = threading.get_ident()
        if _open.get(tid) is self:
            del _open[tid]
        with _lock:
            if self.ident is not None:
                for old in reversed(_log):
                    if old.ident == self.ident and old.parts is not None:
                        self.key_diff = [
                            n for n, v in self.parts.items()
                            if old.parts.get(n) != v]
                        break
            _log.append(self)

    def as_dict(self):
        top = sorted(self.nested.items(), key=lambda kv: -kv[1][1])
        d = {"fingerprint": self.fingerprint, "kind": self.kind,
             "name": self.name, "iters": self.iters, "t0": self.t0,
             "t1": self.t1, "level": self.level,
             "persistent_hit": self.persistent_hit,
             "phases": dict(self.phases),
             "nested_traces": {k: list(v) for k, v in top[:NESTED_KEPT]},
             "key_diff": list(self.key_diff)}
        if self.cause is not None:
            d["cause"] = self.cause
        return d


def open_build(kind, name, fingerprint, ident, parts, iters):
    """The build that is open on this thread from now on. One left open
    by an exception between `prepare_step` and `finish_step` is closed as
    `failed` first."""
    tid = threading.get_ident()
    left = _open.get(tid)
    if left is not None:
        left.close(cause="failed")
    _open[tid] = build = Build(kind, name, fingerprint, ident, parts, iters)
    return build


def _on_duration(event, seconds, fun_name=None, **_kw):
    _entries[0] += 1
    tid = threading.get_ident()
    phase = _PHASE_OF.get(event)
    if phase is None:
        if event == RETRIEVAL:
            _served.add(tid)     # that request's backend event comes next
        return
    served = phase == "backend" and tid in _served
    if served:
        _served.discard(tid)
    build = _open.get(tid)
    if build is not None:
        build._event(phase, fun_name, seconds, served)
    elif fun_name in _WRAP_OF:
        _retrace(tid, phase, _WRAP_OF[fun_name], seconds, served)


def _retrace(tid, phase, name, seconds, served):
    """A wrap's event with no build open: one record a retrace, which its
    trace event opens and its lowering and backend events join."""
    end = time.perf_counter()
    rec = _retraced.get(tid)
    if phase == "trace" or rec is None or rec.name != name \
            or rec.phases[phase] > 0.0:
        rec = _retraced[tid] = Build(None, name, cause="retrace")
        rec.t0 = end - seconds
        with _lock:
            _log.append(rec)
    rec.phases[phase] += seconds
    rec.t1 = end
    if phase == "backend":
        rec.persistent_hit = served
    # what lies between its events: the phases tile it like a build's
    rec.phases["self"] = 0.0
    rec.phases["self"] = max(0.0, end - rec.t0 - sum(rec.phases.values()))


def install():
    """Register the listener; the first `CompileCache` of a process does."""
    with _lock:
        if _installed[0]:
            return
        _installed[0] = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def listener_entries():
    """How often the listener was entered, this process (the tests hold a
    steady state to zero new entries)."""
    return _entries[0]


def build_log(kind=None):
    """The closed builds and the retraces of this process, newest last, as
    dicts; at most LOG_CAP are kept. `kind`: one executor kind's alone."""
    with _lock:
        records = list(_log)
    return [b.as_dict() for b in records if kind is None or b.kind == kind]


def reset():
    """Forget the closed builds (a test that runs two cells in one
    process; the benchmark runs one a process)."""
    with _lock:
        _log.clear()


def open_builds():
    """The builds open right now on any thread, as dicts (`t1` None,
    `self` not yet formed): which phases of a compile that hangs are
    behind it."""
    return [b.as_dict() for b in list(_open.values())]
