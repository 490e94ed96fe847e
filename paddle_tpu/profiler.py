"""Profiler (reference python/paddle/fluid/profiler.py + platform/profiler.cc).

TPU-native: wraps jax.profiler (XPlane/Perfetto traces of XLA executions —
the CUPTI device-tracer equivalent) plus a host-side event table mirroring
the reference's RecordEvent aggregation (profiler.cc:326 ParseEvents) so
`profiler(...)` prints the familiar per-op summary for eager runs.
"""

import contextlib
import threading
import time
from collections import defaultdict

import jax

__all__ = ["cuda_profiler", "reset_profiler", "profiler", "start_profiler",
           "stop_profiler", "record_event", "record_counter",
           "record_bytes", "export_chrome_trace"]

_host_events = []  # (name, start, end)
_counter_events = []  # (name, t, value) — chrome-trace "C" counter samples
_byte_totals = defaultdict(float)  # name -> cumulative bytes (record_bytes)
# one lock for the counter/byte tables: datapipe feeder threads and the
# executor thread report concurrently, and a record_bytes total-update +
# sample-append must be atomic or a racing thread publishes a stale
# cumulative point (a dip in a monotone MB track)
_rec_lock = threading.Lock()
_enabled = False
_trace_dir = None
_last_trace_dir = None  # survives stop_profiler so export can merge
_trace_t0 = None  # perf_counter at jax trace start (lane alignment origin)


class _Event:
    __slots__ = ("name", "start", "end")

    def __init__(self, name):
        self.name = name
        self.start = time.perf_counter()
        self.end = None


@contextlib.contextmanager
def record_event(name):
    """RAII host event (reference platform/profiler.h:72 RecordEvent)."""
    ev = _Event(name)
    try:
        yield
    finally:
        ev.end = time.perf_counter()
        if _enabled:
            _host_events.append(ev)


def record_counter(name, value):
    """Sample a named counter (e.g. a datapipe queue depth); rendered as a
    chrome-trace counter track ("ph": "C") in export_chrome_trace."""
    if _enabled:
        with _rec_lock:
            _counter_events.append((name, time.perf_counter(), float(value)))


def record_bytes(name, nbytes):
    """Accumulate a named byte flow (e.g. one datapipe transfer lane's link
    bytes); rendered as a cumulative MB counter track in the merged chrome
    trace, so per-link throughput reads off the track's slope."""
    if _enabled:
        with _rec_lock:
            _byte_totals[name] += float(nbytes)
            _counter_events.append(
                (name + "/MB", time.perf_counter(),
                 _byte_totals[name] / 1e6))


def reset_profiler():
    global _last_trace_dir, _trace_t0
    del _host_events[:]
    with _rec_lock:
        del _counter_events[:]
        _byte_totals.clear()
    _last_trace_dir = None
    _trace_t0 = None


def start_profiler(state="All", trace_dir=None):
    global _enabled, _trace_dir, _last_trace_dir, _trace_t0
    _enabled = True
    # a fresh session must not inherit the previous session's device trace
    # or its time origin (stale merge + mis-shifted host spans otherwise)
    _last_trace_dir = None
    _trace_t0 = None
    if trace_dir:
        _trace_dir = trace_dir
        _last_trace_dir = trace_dir
        # the device trace only: this module's own events and the trace
        # lane (paddle_tpu.trace) are the host side. JAX's host tracer
        # logs one event per 48 bytes of every host-to-device copy on the
        # v5e runtime (19 GB of host memory, 115 s to stop, copies 30x
        # slower for a datapipe run: PERF.md section 6, PR 23). XLA:CPU
        # alone keeps it: its "device" work runs on host threads and is
        # in no other lane.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        if jax.default_backend() != "cpu":
            opts.host_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # the device trace's ts origin is (approximately) this instant;
        # host events are shifted to the same origin when exporting
        _trace_t0 = time.perf_counter()


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    global _enabled, _trace_dir
    _enabled = False
    if _trace_dir:
        jax.profiler.stop_trace()
        _trace_dir = None
    _print_summary(sorted_key, profile_path)


def _print_summary(sorted_key, profile_path):
    if not _host_events:
        return
    stats = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])  # calls, total, min, max
    for ev in _host_events:
        s = stats[ev.name]
        dur = (ev.end - ev.start) * 1000.0
        s[0] += 1
        s[1] += dur
        s[2] = min(s[2], dur)
        s[3] = max(s[3], dur)
    items = list(stats.items())
    key_fn = {
        "calls": lambda kv: -kv[1][0],
        "total": lambda kv: -kv[1][1],
        "max": lambda kv: -kv[1][3],
        "min": lambda kv: -kv[1][2],
        "ave": lambda kv: -(kv[1][1] / kv[1][0]),
    }.get(sorted_key, lambda kv: -kv[1][1])
    items.sort(key=key_fn)
    header = f"{'Event':<40}{'Calls':>8}{'Total(ms)':>12}{'Min(ms)':>10}{'Max(ms)':>10}{'Ave(ms)':>10}"
    lines = [header, "-" * len(header)]
    for name, (calls, total, mn, mx) in items:
        lines.append(
            f"{name:<40}{calls:>8}{total:>12.4f}{mn:>10.4f}{mx:>10.4f}{total / calls:>10.4f}"
        )
    report = "\n".join(lines)
    print(report)
    try:
        with open(profile_path + ".txt", "w") as f:
            f.write(report)
    except OSError:
        pass


_DEVICE_PID_BASE = 100  # keep device pids clear of the host lane's pid 0


def _load_device_trace(trace_dir):
    """Newest run's Chrome-trace events from a jax.profiler trace_dir
    (plugins/profile/<run>/<host>.trace.json.gz), pids offset into the
    device range. Returns [] when no trace was captured."""
    import glob
    import gzip
    import json

    runs = sorted(glob.glob(
        f"{trace_dir}/plugins/profile/*/*.trace.json.gz"))
    if not runs:
        return []
    with gzip.open(runs[-1], "rt") as f:
        raw = json.load(f).get("traceEvents", [])
    shifted = []
    for e in raw:
        if not isinstance(e, dict) or "pid" not in e:
            continue
        e = dict(e)
        e["pid"] = _DEVICE_PID_BASE + int(e["pid"])
        shifted.append(e)
    return shifted


def export_chrome_trace(path):
    """ONE merged chrome://tracing / Perfetto JSON with BOTH lanes — host
    RecordEvent spans and the XLA device trace (reference
    tools/timeline.py:36-97, which merges host events with CUPTI device
    records via device_tracer.cc:44 the same way).

    Alignment: the device trace's timestamps start at ~0 at
    jax.profiler.start_trace; host events are shifted onto that origin
    (perf_counter delta from start_profiler). Host rows live under pid 0,
    device processes keep their own pids offset by 100."""
    import json

    t0 = _trace_t0 if _trace_t0 is not None else (
        min((ev.start for ev in _host_events), default=0.0))
    events = [
        {"ph": "M", "pid": 0, "name": "process_name",
         "args": {"name": "paddle_tpu host"}},
        {"ph": "M", "pid": 0, "name": "process_sort_index",
         "args": {"sort_index": 0}},
    ]
    for ev in _host_events:
        events.append({
            "name": ev.name,
            "ph": "X",  # complete event
            "ts": (ev.start - t0) * 1e6,
            "dur": (ev.end - ev.start) * 1e6,
            "pid": 0,
            "tid": "host",
            "cat": "host",
        })
    for name, t, value in _counter_events:
        events.append({
            "name": name,
            "ph": "C",
            "ts": (t - t0) * 1e6,
            "pid": 0,
            "args": {"value": value},
        })
    # third lane: flight-recorder spans (pid 1) on the same time origin —
    # serve/datapipe/step spans line up against host events and the
    # device trace
    try:
        from . import trace as _trace_mod

        spans, _dropped = _trace_mod.snapshot()
        if spans:
            events.extend(_trace_mod.chrome_events(spans, t0=t0))
    except Exception:
        pass
    if _last_trace_dir:
        events.extend(_load_device_trace(_last_trace_dir))
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)
    return path


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """API parity with reference profiler.py:33; maps to a jax trace.

    output_file names the trace DIRECTORY (honoured as given — the old
    '"/" in str(...)' heuristic silently redirected bare names to
    /tmp/jax_trace). The dir is also published as _last_trace_dir with the
    session's time origin, so a following export_chrome_trace merges this
    block's device lane instead of dropping it."""
    global _last_trace_dir, _trace_t0
    trace_dir = str(output_file) if output_file else "/tmp/jax_trace"
    jax.profiler.start_trace(trace_dir)
    _last_trace_dir = trace_dir
    if _trace_t0 is None:
        # keep an enclosing start_profiler's origin; otherwise this block
        # defines the merged timeline's zero
        _trace_t0 = time.perf_counter()
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def profiler(state, sorted_key=None, profile_path="/tmp/profile"):
    """reference profiler.py:76. state in {'CPU','GPU','All'} — on TPU all
    states enable the jax trace + host events."""
    start_profiler(state, trace_dir="/tmp/jax_trace")
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)
