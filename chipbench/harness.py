"""The harness: runs ONE cell of BENCHMARK.json once, in this process, and
prints the contract's last line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file the harness finds by the name BENCHMARK.json
gives it, so a later PR adds a cell by adding files and one entry:

    chipbench/configs/<config>.json      sizes, as run (+ <config>.py: how
                                         the system is handed the model)
    chipbench/reference/<config>.py      the plain float32 reference
    chipbench/traffic/<traffic>.json     the mix's parameters; its "kind"
                                         names the general generator
                                         chipbench/kinds/<kind>.py
    chipbench/layer_metrics/<name>.py    read(obs) -> number (`<traffic
                                         prefix>.<base>` falls back to
                                         <base>.py); None = nothing to
                                         read, which on the chip ends the
                                         run with no result: the cell
                                         lists the metric (PR 48)
"""

import contextlib
import importlib.util
import json
import math
import os
import sys
import threading
import time
import types

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Refused(Exception):
    """The cell cannot be measured here (no chip, missing file): the run
    exits non-zero and prints no result."""


def repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_module(path, name=None):
    """A Python file by path: metric names hold dots, so the import
    statement cannot find their readers."""
    if not os.path.exists(path):
        return None
    name = name or "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path):
    with open(path) as f:
        return json.load(f)


def json_objects(text):
    """The JSON objects among the lines of a run's output, in order (a
    run prints progress and warnings between them)."""
    found = []
    for ln in text.splitlines():
        ln = ln.strip()
        if ln.startswith("{"):
            try:
                found.append(json.loads(ln))
            except ValueError:
                pass
    return found


class Files:
    """Where the harness looks things up; `base` is the directory that
    holds configs/, traffic/, kinds/, layer_metrics/ (a test points it at
    a temporary directory, with the shipped one as fall-back)."""

    def __init__(self, root=None, bench_path=None, extra_base=None):
        self.root = root or repo_root()
        self.bench_path = bench_path or os.path.join(
            self.root, "BENCHMARK.json")
        self.bases = [b for b in (extra_base, os.path.dirname(
            os.path.abspath(__file__))) if b]

    def find(self, *parts):
        for base in self.bases:
            p = os.path.join(base, *parts)
            if os.path.exists(p):
                return p
        return None

    def bench(self):
        return load_json(self.bench_path)

    def cell(self, name):
        bench = self.bench()
        cell = next((w for w in bench["workloads"] if w["name"] == name),
                    None)
        if cell is None:
            raise Refused(f"no workload {name!r} in {self.bench_path}")
        entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
        cfg_path = os.path.join(self.root, entry["file"])
        if not os.path.exists(cfg_path):
            cfg_path = self.find("configs", cell["config"] + ".json")
        cfg = load_json(cfg_path)
        traffic_path = self.find("traffic", cell["traffic"] + ".json")
        if traffic_path is None:
            raise Refused(f"no traffic file for {cell['traffic']!r}")
        traffic = load_json(traffic_path)
        builder = load_module(
            os.path.join(self.root, cfg["builder"])
            if os.path.exists(os.path.join(self.root, cfg["builder"]))
            else self.find("configs", cell["config"] + ".py"))
        kind = load_module(self.find("kinds", traffic["kind"] + ".py"))
        if builder is None or kind is None:
            raise Refused(f"cell {name!r}: builder or kind file missing")
        return bench, cell, cfg, traffic, builder, kind

    def metric_reader(self, name):
        path = self.find("layer_metrics", name + ".py")
        if path is None and "." in name:
            path = self.find("layer_metrics", name.split(".", 1)[1] + ".py")
        return load_module(path) if path else None


def host_rss_gb():
    """This process's resident memory, GB (0.0 where /proc is absent)."""
    try:
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmRSS:"):
                    return int(ln.split()[1]) / 1e6
    except OSError:
        pass
    return 0.0


def note(msg, t_start=None, file=sys.stderr):
    """A progress line on standard error: what a killed run got to."""
    at = f"+{time.perf_counter() - t_start:7.1f}s " if t_start else ""
    print(f"[chipbench {at}rss {host_rss_gb():5.1f} GB] {msg}", file=file,
          flush=True)


class Setup:
    """Where `setup_s` went: named, non-overlapping stretches of set-up."""

    def __init__(self, t_start):
        self.t_start = t_start
        self.items = []

    @contextlib.contextmanager
    def item(self, name):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t)

    def add(self, name, seconds):
        self.items.append([name, seconds])
        note(f"{name} {seconds:.2f}s", self.t_start)

    def itemised(self, setup_s):
        named = sum(s for _, s in self.items)
        return {"setup_s": setup_s, "items": self.items,
                "not_itemised_s": setup_s - named}


class CompileLog:
    """Compile requests that reached the backend, as JAX itself reports
    them (the method of chip_smoke.py): one `backend_compile` duration is
    one request, served by the persistent cache when a `cache_hits` event
    came just before it on the same thread."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._pending = threading.local()
        self.events = []          # (name, seconds, from_persistent_cache)
        self._jax = jax
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_kw):
        if event == CACHE_HIT:
            self._pending.hit = True

    def _on_duration(self, event, secs, **kw):
        if event != BACKEND_COMPILE:
            return
        hit = getattr(self._pending, "hit", False)
        self._pending.hit = False
        with self._lock:
            self.events.append((str(kw.get("fun_name", "?")), secs, hit))

    def close(self):
        self._jax.monitoring.unregister_event_listener(self._on_event)
        self._jax.monitoring.unregister_event_duration_listener(
            self._on_duration)

    def mark(self):
        with self._lock:
            return len(self.events)

    def since(self, mark=0):
        with self._lock:
            ev = self.events[mark:]
        return {"requests": len(ev),
                "persistent_hits": sum(1 for e in ev if e[2]),
                "seconds": sum(e[1] for e in ev),
                "slowest": [[e[0], e[1]] for e in
                            sorted(ev, key=lambda e: -e[1])[:3]]}


class SpanLog:
    """Host spans on `perf_counter`, kept in memory while a trace runs.
    The profiler's own host tracer is off: on this runtime it records one
    event per 48 bytes of every host-to-device copy (806,850 `Transpose`
    events for two 19 MB batches; a 15 s window of the pipe cell wrote
    850 MB, took 115 s to stop and 19 GB of host memory: PR 23)."""

    def __init__(self):
        self.active = False
        self.spans = []

    @contextlib.contextmanager
    def span(self, name):
        if not self.active:
            yield
            return
        t = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append(["chipbench." + name, t, time.perf_counter(),
                               threading.current_thread().name])


SPANS = SpanLog()


def span(name):
    """A host span around one call into a layer; free when no trace runs."""
    return SPANS.span(name)


class Tracer:
    """One traced window: device trace on, clock markers, the window,
    markers, off, reduce. The trace lives under the checkout's work
    directory and is deleted once it is reduced."""

    def __init__(self, workdir, enabled, device=None, keep=None):
        self.dir = os.path.join(workdir, "trace")
        self.enabled = enabled
        self.device = device
        self.reduced = None
        self.host = {"spans": SPANS.spans, "window": None, "syncs": []}
        self.keep = keep          # `--dump`: the raw trace is copied there

    def _mark(self, times):
        import jax

        for _ in range(times):
            before = time.perf_counter()
            jax.block_until_ready(self._marker(self._one))
            self.host["syncs"].append([before, time.perf_counter()])

    def start(self):
        if not self.enabled:
            return
        import shutil

        import jax
        import jax.numpy as jnp

        def chipbench_clock_marker(x):
            return x + 1

        self._marker = jax.jit(chipbench_clock_marker)
        self._one = jax.device_put(jnp.zeros((8, 128), jnp.float32),
                                   self.device)
        jax.block_until_ready(self._marker(self._one))   # compiled here
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._mark(3)
        SPANS.spans.clear()
        SPANS.active = True
        self._t0 = time.perf_counter()

    def stop(self):
        if not self.enabled:
            return
        import glob
        import shutil

        import jax

        from chipbench import xplane

        self.host["window"] = [self._t0, time.perf_counter()]
        SPANS.active = False
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        size = os.path.getsize(files[0]) if files else 0
        note(f"trace written: {size / 1e6:.1f} MB")
        if files:
            self.reduced = xplane.reduce_file(files[0], host=self.host)
            if self.keep:
                os.makedirs(self.keep, exist_ok=True)
                shutil.copy(files[0], os.path.join(self.keep,
                                                   "window.xplane.pb"))
                with open(os.path.join(self.keep, "window.host.json"),
                          "w") as f:
                    json.dump(self.host, f)
        shutil.rmtree(self.dir, ignore_errors=True)
        note("trace reduced" if self.reduced else "trace NOT reduced")


def reap_children(timeout_s=10.0):
    """Every process this run started has ended when this returns: the
    datapipe's decode workers are normally gone once the pipe is closed;
    one that is not is terminated, then killed. Returns how many needed
    that."""
    import multiprocessing

    forced = 0
    for p in multiprocessing.active_children():
        p.join(timeout=timeout_s)
        if p.is_alive():
            forced += 1
            p.terminate()
            p.join(timeout=timeout_s)
            if p.is_alive():
                p.kill()
                p.join()
    return forced


def leave(grace_s):
    """The result is printed and the children are gone; what is left is
    the interpreter's own shutdown (joining threads, closing the TPU
    client), which once never returned after a 51 s window (PR 23). If it
    takes longer than `grace_s`, say where it hangs and end the process."""
    import faulthandler

    def bite():
        time.sleep(grace_s)
        note("interpreter shutdown hangs; thread stacks follow, then exit")
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        sys.stderr.flush()
        os._exit(0)

    threading.Thread(target=bite, name="chipbench-leave",
                     daemon=True).start()


def pick_devices(chips, rehearsal):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearsal:
        raise Refused(f"no TPU: JAX found {devs}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def memory_peak(devices):
    """Peak device memory on the fullest chip: live buffers plus what the
    runtime reserved for the programs' temporaries. On this runtime
    `peak_bytes_in_use` leaves the temporaries (a training step's
    activations) out and counts them under `peak_bytes_reserved`: a jit
    with 1.61 GB of temporaries over a 0.54 GB argument read 0.54 in use
    and 1.61 reserved (my chip run, PR 23)."""
    peaks = []
    for d in devices:
        m = d.memory_stats() or {}
        peaks.append(m.get("peak_bytes_in_use", 0)
                     + m.get("peak_bytes_reserved", 0))
    return int(max(peaks)) if peaks else 0


def _strict(v):
    """`v` with every float JSON has no word for (inf, nan: a reading of a
    run that is not `correct`) as its name: the result's line is read by
    strict parsers."""
    if isinstance(v, float) and not math.isfinite(v):
        return repr(v)
    if isinstance(v, dict):
        return {k: _strict(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_strict(x) for x in v]
    return v


def run_cell(workload, seed, seconds, trace, t_start=None, rehearsal=False,
             override=None, files=None, dump=None, out=sys.stdout):
    """Runs the cell and prints its lines to `out`; returns the result
    dict (the last line). `rehearsal` is the only way the harness accepts
    a non-TPU device (the CPU tests); `override` replaces keys of the
    configuration and the traffic file (tests, sweeps, studies). The
    result says either."""
    t_start = time.perf_counter() if t_start is None else t_start
    files = files or Files()
    setup = Setup(t_start)
    with setup.item("import_jax"):
        import jax
    with setup.item("read_cell_files"):
        bench, cell, cfg, traffic, builder, kind = files.cell(workload)
        if override:
            cfg = dict(cfg, **override.get("config", {}))
            traffic = dict(traffic, **override.get("traffic", {}))
    with setup.item("device_client_init"):
        devices = pick_devices(int(cell["chips"]), bool(rehearsal))
    from chipbench import costs

    kind0 = devices[0].device_kind
    peaks = costs.peaks_for(kind0) if not rehearsal else \
        costs.peaks_for("TPU v5 lite")
    with setup.item("import_paddle_tpu"):
        import paddle_tpu as fluid
        from paddle_tpu.cache import place_jax_cache

        # JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache:
        # a fixed path inside the checkout, the same every run
        cache_dir = place_jax_cache()
    workdir = os.path.join(files.root, ".chipbench_work")
    os.makedirs(workdir, exist_ok=True)
    log = CompileLog()
    # what a kind's runner is handed
    ctx = types.SimpleNamespace(
        fluid=fluid, jax=jax, cell=cell, cfg=cfg, traffic=traffic,
        builder=builder, devices=devices, chips=len(devices),
        seed=int(seed), seconds=float(seconds), trace=bool(trace),
        setup=setup, log=log, workdir=workdir, dump=dump, t_start=t_start,
        tracer=Tracer(workdir, bool(trace), devices[0], keep=dump))
    try:
        res = kind.run(ctx)
    finally:
        log.close()
        forced = reap_children()
        if forced:
            note(f"{forced} child process(es) had to be terminated")
    setup_s = res["t_open"] - t_start
    device = {"platform": devices[0].platform, "kind": kind0,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak(devices)}
    obs = dict(res, cfg=cfg, traffic=traffic, peaks=peaks, cell=cell,
               chips=len(devices), trace=ctx.tracer.reduced, device=device)
    metrics, missing, notes = {}, [], {}
    names = (bench["per_layer"] if trace else bench["end_to_end"])
    for m in names:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        if m["name"] == "setup_s":
            value = setup_s
        elif not trace:
            value = res["end_to_end"].get(m["name"])
        else:
            reader = files.metric_reader(m["name"])
            value = reader.read(obs) if reader is not None else None
            # what a reader has to say beside its number (`note(obs)`,
            # where it has one: the grouped kernels' events against those
            # wanted): into the run's detail and on standard error
            said = getattr(reader, "note", lambda obs: None)(obs)
            if said:
                notes[m["name"]] = said
                note(f"{m['name']}: {json.dumps(said)}")
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        else:
            missing.append(m["name"])
    print(json.dumps({"chipbench_setup": setup.itemised(setup_s),
                      "compile_cache_dir": cache_dir,
                      "setup_compile": res["setup_compile"]}), file=out)
    print(json.dumps({"chipbench_detail": dict(
        res.get("detail", {}), **({"layer_metric_notes": notes}
                                  if notes else {})),
                      "reference": res.get("reference")}), file=out)
    if missing and not rehearsal:
        # the contract wants every metric the cell lists: a line that
        # lacks one is refused as malformed (PR 47), so say which reader
        # found nothing and print no result at all. (A rehearsal on the
        # CPU has no device plane: its line names what it left out.)
        def where(name):
            if not trace:
                return "the kind's end_to_end"
            reader = files.metric_reader(name)
            return os.path.relpath(reader.__file__, files.root) \
                if reader else "no reader file"

        raise Refused(
            f"cell {cell['name']!r}, --trace {int(bool(trace))}: "
            + "; ".join(f"{n!r} ({where(n)}) read None" for n in missing)
            + ": the line would lack a metric the cell lists, so no "
            "result is printed")
    line = {"correct": bool(res["correct"]),
            "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": metrics,
            "device": device, "workload": cell["name"], "seed": int(seed),
            "checks": res.get("checks", {})}
    if rehearsal:
        line["rehearsal"] = True
        line["metrics_missing"] = missing
    if override:
        line["override"] = override
    if trace and ctx.tracer.reduced:
        r = ctx.tracer.reduced
        device["busy_s"] = r["busy_s"]
        device["window_s"] = r["window_s"]
        line["breakdown"] = {"device_ops": r["device_ops"],
                             "idle_gaps": r["idle_gaps"]}
    # the numbers the comparison held beside their limits, where its
    # report pairs them (`compared`): the line's last key and the run's
    # last lines on standard error, which is what the driver keeps of a
    # run that is not `correct`
    report = res.get("reference") or {}
    compared = report.get("compared")
    if not compared and "failed" in report:
        # a comparison that pairs no numbers yet (the olmoe, laguna and
        # lfm2 cells'): the checks that failed by name, the report's own
        # scalar readings and its limits, so that a refused run of theirs
        # says more than `reference: false`
        line["compared"] = {
            "failed": list(report["failed"]),
            "readings": {k: v for k, v in report.items()
                         if isinstance(v, (int, float))
                         and not isinstance(v, bool)},
            "limits": report.get("limits", {})}
        for name, value in line["compared"].items():
            print(f"[chipbench compared] {name}: {json.dumps(value)}",
                  file=sys.stderr, flush=True)
    if compared:
        from chipbench import held

        line["compared"] = dict(
            compared, failed=list(res["reference"].get("failed", ())))
        for name, value in line["compared"].items():
            print(f"[chipbench compared] {name}: "
                  + (f"{value[0]} (limit {value[1]})" if name != "failed"
                     else json.dumps(value)), file=sys.stderr, flush=True)
        # the numbers that fail once more, LAST: the driver keeps the end
        # of standard error, and a comparison holds more numbers than that
        # end has room for
        for name, value in compared.items():
            if held.fails(*value):
                print(f"[chipbench compared] FAILS {name}: {value[0]} "
                      f"(limit {value[1]})", file=sys.stderr, flush=True)
    if "compared" in line:
        line["compared"] = _strict(line["compared"])
    print(json.dumps(line), file=out, flush=True)
    return line
