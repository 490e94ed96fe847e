"""Python Executor (reference python/paddle/fluid/executor.py:181).

run() compiles the whole program into one XLA computation (see
core/executor_core.py) and caches the compiled step keyed by
(program identity+mutation, feed signature, fetch names). Programs containing
host-side ops (save/load/print/readers/listen_and_serv) run in the eager
interpret mode, matching the reference's op-by-op Executor semantics.
"""

import collections
import time

import numpy as np
import jax
import jax.numpy as jnp

from . import analysis
from . import flags
from . import monitor
from .cache import CompileCache, place_jax_cache
from .core import executor_core, registry
from .core.framework import Program, Variable, default_main_program
from .core.lod_tensor import LoDTensor
from .core.places import CPUPlace, TPUPlace, jax_device_for
from .core.scope import global_scope, Scope
from .core.registry import SeqTensor
from . import health as _health
from .resilience import chaos as _chaos
from .resilience import watchdog as _watchdog
from . import trace as _trace

__all__ = ["Executor", "FetchFuture", "global_scope", "scope_guard",
           "fetch_var"]

from .core.scope import scope_guard  # re-export (reference executor.py:39)

flags.define(
    "donate_feed_buffers", bool, True,
    "Donate single-use staged feed chunks (datapipe transfer engine marks "
    "them) to the compiled step so XLA reclaims their staging HBM for the "
    "next transfer instead of holding it across the dispatch. Off: staged "
    "chunks stay readable after run() (debugging).")


def _ensure_addressable(arr):
    """A jax.Array sharded over a cross-process mesh cannot be read locally;
    all-gather it to every process first (collective — every process fetches
    the same names in SPMD lockstep, the reference NCCL2-mode contract)."""
    if getattr(arr, "is_fully_addressable", True):
        return arr
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(arr, tiled=True)


def as_numpy(tensor):
    if isinstance(tensor, LoDTensor):
        if tensor.lod():
            return tensor  # ragged: return LoDTensor like the reference
        return tensor.numpy()
    if isinstance(tensor, (list, tuple)):
        return [as_numpy(t) for t in tensor]
    return np.asarray(_ensure_addressable(tensor))


def fetch_var(name, scope=None, return_numpy=True):
    scope = scope or global_scope()
    v = scope.find_var(name)
    if v is None:
        raise ValueError(f"Variable {name!r} is not found in scope")
    if return_numpy:
        if isinstance(v, SeqTensor):
            return np.asarray(v.data)
        return np.asarray(_ensure_addressable(v))
    return v


_debug_nans_applied = [None]


def _apply_debug_nans():
    """Sync the debug_nans flag into jax config (cheap no-op when
    unchanged); FLAGS_debug_nans can flip between runs like the
    reference's runtime gflags."""
    want = flags.get("debug_nans")
    if _debug_nans_applied[0] != want:
        jax.config.update("jax_debug_nans", bool(want))
        _debug_nans_applied[0] = want


def _program_has_host_ops(program):
    for block in program.blocks:
        for op in block.ops:
            op_def = registry.get_op_def(op.type)
            if op_def is not None and op_def.no_trace:
                return True
    return False


def stack_multi_step_feeds(program, feed, iters, wire=None):
    """list-of-dicts -> one dict of [K, ...] jnp arrays for an iters=K scan
    (shared by Executor and ParallelExecutor); a dict is trusted to be
    pre-stacked (leading axis == iters, checked). Sequence feeds ride too:
    SeqTensors (e.g. from create_bucketed_seq_tensor) whose K steps share
    one (ntokens, batch) shape stack componentwise — SeqTensor is a pytree,
    so lax.scan slices the leading axis of data and lengths together.
    Ragged feeds whose shapes differ across steps are rejected with a
    pointer to the bucketing bridge. Dense feeds cast to each program
    var's declared dtype — except names covered by a datapipe WireSpec,
    which cross the link in their compact wire dtype and are decoded
    inside the compiled step (per scan iteration, so the full-width
    tensor never materialises as [K, ...] in HBM)."""
    import jax.numpy as jnp

    if isinstance(feed, (list, tuple)):
        if len(feed) != iters:
            raise ValueError(
                f"iters={iters} but feed has {len(feed)} step dicts")
        names = set().union(*(f.keys() for f in feed)) if feed else set()
        stacked = {}
        for n in names:
            if any(n not in f for f in feed):
                raise ValueError(
                    f"feed {n!r} missing from some step dicts (every "
                    f"iters=K step must feed the same names)")
            vals = [f[n] for f in feed]
            if any(isinstance(v, SeqTensor)
                   or (isinstance(v, LoDTensor) and v.lod())
                   for v in vals):
                seqs = [executor_core.feed_to_tracevalue(v) for v in vals]
                if not all(isinstance(s, SeqTensor) for s in seqs):
                    raise ValueError(
                        f"feed {n!r} mixes ragged and dense values across "
                        f"the {iters} steps")
                shapes = {(s.data.shape, s.lengths.shape) for s in seqs}
                if len(shapes) != 1:
                    raise ValueError(
                        f"iters > 1 needs ONE static shape per feed, but "
                        f"ragged feed {n!r} varies across steps "
                        f"({sorted(shapes)}); bucket-and-pad first "
                        f"(fluid.create_bucketed_seq_tensor)")
                stacked[n] = SeqTensor(
                    jnp.stack([s.data for s in seqs], 0),
                    jnp.stack([s.lengths for s in seqs], 0))
                continue
            stacked[n] = np.stack([np.asarray(v) for v in vals], 0)
        feed = stacked
    vals = {}
    gb = program.global_block()
    for name, value in feed.items():
        var = gb.vars.get(name)
        if isinstance(value, SeqTensor):
            # pre-stacked [K, ...] SeqTensor (built above or by the caller)
            if np.shape(value.data)[0] != iters or \
                    np.shape(value.lengths)[0] != iters:
                raise ValueError(
                    f"stacked SeqTensor feed {name!r} must carry a leading "
                    f"[K={iters}] axis on data and lengths, got "
                    f"{np.shape(value.data)} / {np.shape(value.lengths)}")
            vals[name] = value
            continue
        if isinstance(value, LoDTensor) and value.lod():
            raise ValueError(
                f"iters > 1 takes ragged feeds as per-step LIST dicts "
                f"(bucketed to one shape, see "
                f"fluid.create_bucketed_seq_tensor); a single pre-stacked "
                f"LoDTensor ({name!r}) is not supported")
        tv = value if hasattr(value, "dtype") else np.asarray(value)
        if len(np.shape(tv)) == 0:
            raise ValueError(
                f"feed {name!r} is a scalar; iters > 1 feeds must be "
                f"pre-stacked with a leading [K={iters}] axis")
        if np.shape(tv)[0] != iters:
            raise ValueError(
                f"feed {name!r} leading axis {np.shape(tv)[0]} != "
                f"iters {iters} (pre-stacked feeds carry [K, ...])")
        tv = jnp.asarray(tv)
        if var is not None and var.dtype is not None \
                and str(tv.dtype) != var.dtype \
                and not (wire is not None and name in wire):
            tv = tv.astype(var.dtype)
        vals[name] = tv
    return vals


def to_host(value):
    if isinstance(value, SeqTensor):
        return executor_core.value_to_lod_tensor(value)
    return value


def step_rng(program, step0, iters=None):
    """The rng of the step numbered `step0`: folded on the host for one
    step; (base, step0) for a scan, whose step i folds base at step0 + i
    itself (executor_core.build_multi_step_fn) — the stream of `iters`
    sequential calls. step0 rides as a traced array: a Python int would
    bake into the computation and compile again every call."""
    base = jax.random.PRNGKey(program.random_seed)
    if iters is None:
        return jax.random.fold_in(base, step0)
    return base, jnp.asarray(step0, jnp.int32)


# ---------------------------------------------------------------------------
# The compiled-step path of Executor.run and ParallelExecutor.run, in call
# order: begin_step, [the caller's feed values], prepare_step, split_state,
# [the caller's rng and its call of step.compiled], finish_step, end_step.
# Between them the monitor's laps tile the step.
# ---------------------------------------------------------------------------
def begin_step(kind, feed, iters, donate_feeds, fetch_list):
    """The head of run(): the step's record, the pull from a
    datapipe.DataPipe (anything with next_feed(); the step's `feed_wait`
    lap), the transfer engine's markers off the chunk, the donation gate
    and the fetch names. The record is None when FLAGS_monitor and
    FLAGS_trace are both off (two flag reads), and every telemetry site
    gates on `mon is not None`; its laps tile the step: each closes the
    stretch since the one before under the phase's name.
    Returns (mon, pipe, feed, iters, wire, donate_feeds, fetch_names)."""
    _apply_debug_nans()
    monitored = monitor.enabled()
    mon = monitor.step_begin(kind, monitored) \
        if monitored or _trace.enabled() else None
    pipe = feed if hasattr(feed, "next_feed") else None
    if pipe is not None:
        if iters is None:
            iters = getattr(pipe, "feed_iters", None)
        feed = pipe.next_feed()
        if mon is not None:
            mon.lap("feed_wait")
    from .datapipe.transfer import pop_markers
    feed, wire, chunk_donate = pop_markers(feed)
    if donate_feeds is None:
        donate_feeds = chunk_donate
    # under debug_nans jax re-runs the step op by op and needs its inputs
    donate_feeds = bool(donate_feeds) \
        and bool(flags.get("donate_feed_buffers")) \
        and not flags.get("debug_nans")
    fetch_names = [v.name if isinstance(v, Variable) else str(v)
                   for v in fetch_list or []]
    return mon, pipe, feed, iters, wire, donate_feeds, fetch_names


def end_step(mon, outs, pipe, iters, async_fetch, return_numpy, **replica):
    """The end of run(): the `write_back` lap (scope.set_var over the
    state, health and NaN checks, leaving the watchdog and the device
    scope or mesh), futures or the host read-back, and the record closed.
    `replica`: the ParallelExecutor's replica_ms / replica_ids."""
    if mon is not None:
        mon.lap("write_back")
    if async_fetch:
        outs = [FetchFuture(o) for o in outs]
    elif return_numpy:
        outs = [as_numpy(o) for o in outs]
        if mon is not None:
            mon.lap("fetch_readback")
    if mon is not None:
        monitor.step_end(mon, iters=iters, datapipe=pipe, **replica)
    return outs


def _wire_var_dtypes(program, wire):
    gb = program.global_block()
    out = {}
    for n in wire:
        var = gb.vars.get(n)
        if var is not None and var.dtype is not None:
            out[n] = var.dtype
    return out


# one prepared step: what split_state and finish_step need of prepare_step
_Step = collections.namedtuple(
    "_Step", "compiled program scope fetch_names state_names "
             "state_out_names iters hplan mon kind was_miss build fp")


def prepare_step(cache, program, scope, feed_vals, fetch_names, *, iters,
                 wire, donate_feeds, mon, kind, devices, l2_extra,
                 use_cache=True, key_extra=(), verify=None,
                 constraints=None):
    """The compiled step of `program` for these feeds, fetches and the
    state `scope` holds: from `cache`, or built and stored there.

    devices: the executor's device assignment in mesh order (a loaded
    executable is bound to it). The ParallelExecutor's own: `key_extra`
    (step_key's extra), `verify` (ensure_verified's mesh_axes / zplan /
    aplan), `constraints` (a callable giving build_step_fn's, asked only
    when a step is built). `l2_extra()` gives the device context folded
    into the persistent digest, asked on a miss."""
    # the refresh first: it may put a copy into the scope that the names
    # must include
    executor_core.refresh_kept_copies(program, scope)
    state_names, state_out_names = executor_core.collect_state_names(
        program, scope)
    if mon is not None:
        mon.lap("state_gather")
    # health sees the program as resolved, so under zero1 the plan pairs
    # the canonical param with its reduce-scattered [N, shard] grad —
    # shard-local reductions, no regather (health/stats.py)
    hplan = _health.plan_if_enabled(program)
    ident, content = executor_core.step_key(
        program, feed_vals, fetch_names, state_names, iters=iters,
        wire=wire, donate_feeds=donate_feeds, health=hplan, extra=key_extra)
    cache_key = ident + content
    compiled = cache.get(cache_key) if use_cache else None
    fp = None
    if mon is not None:
        fp = monitor.fingerprint_of(cache_key)
        mon.lap("cache_lookup")
    record, level = None, "l1"
    if compiled is None:
        if fp is None:
            fp = monitor.fingerprint_of(cache_key)
        # the build's record (cache/builds.py), always on: open from here
        # to finish_step, on this thread, so that JAX's trace, lowering
        # and backend events of the first call are filed under it; the
        # name is the jitted wrap's (see `build` below)
        record = cache.open_build(
            "multi" if iters is not None else "health_step"
            if hplan is not None else "wired" if wire is not None
            else "step",
            fp, ident, executor_core.key_parts(content), iters)
        # FLAGS_verify: static checks ride the compile-cache MISS path
        # only (memoized per program+mutation+config), so the enabled
        # flag's steady-state cost is this one dict lookup
        analysis.ensure_verified(
            program, feed_names=list(feed_vals),
            fetch_names=list(fetch_names),
            donate_state=not flags.get("debug_nans"), context=kind,
            **(verify or {}))
        record.lap("verify")

        def build(aot):
            step = executor_core.build_step_fn(
                program,
                list(fetch_names) + hplan.fetch_names
                if hplan is not None else fetch_names,
                state_out_names,
                constraints=constraints() if constraints is not None
                else None)
            if wire is not None:
                # decode INSIDE the per-step fn: a scan slices the compact
                # [K, ...] wire chunk and each iteration casts/scales only
                # its own step's slice — the full-width tensor never
                # exists as [K, ...] in device memory
                step = wire.wrap_step(
                    step, var_dtypes=_wire_var_dtypes(program, wire))
            if hplan is not None:
                # fold the appended grad fetches into one [4]-stat leaf
                # per param INSIDE the jit and BEFORE the scan wraps them:
                # a scan stacks tiny stats, never raw [K, ...] gradients
                # (health/stats.py)
                step = hplan.wrap_step(step, len(fetch_names))
            if iters is not None:
                step = executor_core.build_multi_step_fn(step, iters)
            probe = monitor.compile_probe(fp) \
                if mon is not None and mon.monitored \
                and flags.get("monitor_hlo_cost") else None
            # under debug_nans the trap fires INSIDE compiled() before
            # the scope write-back; donated buffers would already be
            # deleted, wrecking both the scope and jax's op-by-op
            # re-run — so trade the in-place update away while the
            # sanitizer is on
            return executor_core.compile_step_fn(
                step, donate_state=not flags.get("debug_nans"),
                donate_feeds=donate_feeds, probe=probe, aot=aot)

        compiled, level = cache.load_or_build(
            cache_key, content=content, program=program, build=build,
            devices=devices, extra=l2_extra(), use_cache=use_cache, mon=mon,
            record=record)
        record.level = level
        if mon is not None:
            # a miss compiles inside the first call as well (async
            # dispatch): both stretches are the `compile` phase
            mon.lap("cache_load" if level == "l2" else "compile")
            mon.build = record
    if mon is not None:
        mon.mark_cache(level is not None, fingerprint=fp, level=level,
                       lowered=executor_core.lowered_counts(
                           program, devices[0]))
    return _Step(compiled, program, scope, fetch_names, state_names,
                 state_out_names, iters, hplan, mon, kind, level is None,
                 record, fp)


def split_state(step, place=None):
    """The scope's values of the step's state as (mut_state, const_state):
    what the step writes, which the jit donates, and what it only reads.
    place(name, value): the ParallelExecutor's placement onto its mesh."""
    if step.iters is not None:
        missing = [n for n in step.state_out_names
                   if not step.scope.has_var(n)]
        if missing:
            raise ValueError(
                f"iters > 1 needs every written persistable var in scope "
                f"before the scan (the carry structure is fixed); missing: "
                f"{missing}. Run the startup program (or one plain "
                f"exe.run) first.")
    mut_state, const_state = {}, {}
    out_set = set(step.state_out_names)
    for n in step.state_names:
        v = step.scope.find_var(n)
        if isinstance(v, LoDTensor):
            v = executor_core.feed_to_tracevalue(v)
        if place is not None:
            v = place(n, v)
        (mut_state if n in out_set else const_state)[n] = v
    return mut_state, const_state


def finish_step(step, fetches, new_mut, step0):
    """After the call of step.compiled: the health leaf off the fetches,
    the call's lap — `dispatch` (enqueue time under async dispatch) on a
    hit or a load from the persistent store, `compile` on a miss, whose
    first call holds the XLA compile — the record of a build closed
    (flags or none), the state written back, the health hook. Returns
    the caller's fetches."""
    mon, hstats = step.mon, None
    if step.hplan is not None:
        hstats, fetches = fetches[-1], fetches[:-1]
    if mon is not None:
        mon.lap("compile" if step.was_miss else "dispatch")
    if step.build is not None:
        # built or loaded in this run(): the build ends with the first
        # call, and its wall is the compile's one stamp
        step.build.close()
        if step.was_miss and mon is not None and mon.monitored:
            monitor.record_compile(step.fp, wall_s=step.build.wall)
    # write back BEFORE any nan check can raise: mut_state was donated,
    # so skipping this would leave the scope holding deleted buffers
    for n, v in new_mut.items():
        step.scope.set_var(n, v)
    executor_core.note_kept_copies(step.program, step.scope, new_mut)
    if hstats is not None:
        _health.on_step(step0, step.iters, hstats, step.fetch_names,
                        fetches, mon=mon, kind=step.kind)
    return fetches


class FetchFuture:
    """Handle to one in-flight fetch from run(async_fetch=True).

    jax dispatch is asynchronous, so the computation is already running on
    the device when run() returns; what a future defers is the HOST
    READBACK. Holding futures lets the caller overlap the next chunk's
    transfer and dispatch with the current scan instead of fencing on a
    device_get every call — fence at most one chunk behind (depth-1
    pipelining) by calling result() on the previous chunk's future.

    value    — the device-side array (or LoDTensor for sequence fetches)
    done()   — True once the device value is computed (no blocking)
    result() — block and return the host value (numpy, matching
               return_numpy=True semantics); cached after the first call
    """

    __slots__ = ("_value", "_host")

    def __init__(self, value):
        self._value = value
        self._host = None

    @property
    def value(self):
        return self._value

    def done(self):
        if self._host is not None:
            return True
        v = self._value
        if isinstance(v, SeqTensor):
            v = v.data
        is_ready = getattr(v, "is_ready", None)
        return bool(is_ready()) if callable(is_ready) else True

    def result(self):
        if self._host is None:
            self._host = as_numpy(self._value)
        return self._host


class Executor:
    def __init__(self, place=None):
        self.place = place if place is not None else TPUPlace(0)
        place_jax_cache()
        self._compile_cache = CompileCache("executor")
        self._step_counter = {}

    def _device_scope(self):
        """Pin execution to the Place's device (executor.cc:133 runs ops on
        the given Place; here every trace, eager dispatch, and feed
        conversion inside run() happens under jax.default_device)."""
        return jax.default_device(jax_device_for(self.place))

    def compile_cache_info(self):
        """Compile-cache stats: entries plus hit/miss/eviction counters and
        the persistent-L2 counter family (cache.CompileCache.info). The
        "entries" key is load-bearing — the serving engine diffs it across
        warmup to assert zero steady-state compiles."""
        return self._compile_cache.info()

    def _l2_extra(self):
        """Device context folded into the persistent-cache digest: a
        serialized executable is bound to its device assignment, so a
        different device takes a clean miss instead of a load failure."""
        dev = jax_device_for(self.place)
        return (("device", getattr(dev, "platform", "?"),
                 int(getattr(dev, "id", -1))),)

    # ------------------------------------------------------------------
    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        feed_var_name="feed",
        fetch_var_name="fetch",
        scope=None,
        return_numpy=True,
        use_program_cache=True,
        iters=None,
        async_fetch=False,
        donate_feeds=None,
    ):
        """Run the program once — or, with `iters=K`, K steps in ONE device
        dispatch (a jit'd lax.scan over the step; the TPU-idiomatic host
        loop). For iters > 1, `feed` is either a list of K per-step feed
        dicts (stacked and transferred in one device_put) or a single dict
        whose arrays already carry a leading [K] axis (may be
        device-resident, e.g. from datapipe.AsyncDeviceFeeder). Fetches come
        back stacked with a leading [K] axis.

        `feed` may also be a datapipe.DataPipe (anything with next_feed()):
        the executor pulls the next prefetched chunk itself and defaults
        iters to the pipe's chunk size (feed_iters). The pipe's
        StopIteration propagates when it is exhausted, and a
        datapipe.DataPipeError (e.g. a decode worker process died and
        FLAGS_datapipe_restart_workers is off) propagates from the pull.
        The wait for the staged chunk is the step's `feed_wait` phase —
        nonzero time there means the device out-ran the pipe; the per-step
        record's `datapipe` delta (stats_delta) names the stage to blame.

        Transfer-engine markers riding in a staged chunk (datapipe
        WIRE_KEY / DONATE_KEY) are honoured: wire-compressed feeds are
        decoded inside the compiled step (cast+scale fused into the scan),
        and single-use chunks are donated so XLA reuses their staging
        memory. `donate_feeds` overrides the chunk's marker (None = follow
        the marker); the FLAGS_donate_feed_buffers flag gates donation
        globally.

        `async_fetch=True` returns a list of FetchFuture instead of host
        arrays: the dispatch has happened, but the host readback is
        deferred until .result(), so the caller can overlap the next
        chunk's transfer with this chunk's compute (return_numpy is
        ignored in that case).
        """
        if program is None:
            program = default_main_program()
        if scope is None:
            scope = global_scope()
        mon, pipe, feed, iters, wire, donate_feeds, fetch_names = \
            begin_step("executor", feed, iters, donate_feeds, fetch_list)
        if isinstance(feed, (list, tuple)) and iters is None:
            iters = len(feed)  # length consistency checked in the helper
        feed = feed if feed is not None else {}
        # fault-injection hook (no-op unless a ChaosMonkey is installed);
        # fires BEFORE the dispatch so donated feed buffers are untouched
        # when an injected transient error reaches the retry layer
        _chaos.on_run("executor")
        with _watchdog.armed("executor"), self._device_scope():
            # ANY explicit iters (including 1) means "feeds carry a
            # leading [K] axis, fetches come back stacked [K, ...]" —
            # routing K=1 to the plain path would feed the stacked
            # array with its bogus leading axis straight into the ops
            if iters is not None and iters < 1:
                raise ValueError(f"iters must be >= 1, got {iters}")
            if not _program_has_host_ops(program):
                outs = self._run_compiled(
                    program, scope, feed, fetch_names, use_program_cache,
                    iters, wire=wire, donate_feeds=donate_feeds, mon=mon)
            elif iters is not None:
                raise ValueError(
                    "iters requires a fully compilable program "
                    "(host-side ops like readers/save/print run "
                    "step-by-step)")
            else:
                if mon is not None:
                    mon.kind = "executor_eager"
                outs = self._run_eager(program, scope, feed, fetch_names,
                                       wire=wire, mon=mon)
        return end_step(mon, outs, pipe, iters, async_fetch, return_numpy)

    # ------------------------------------------------------------------
    def _feed_values(self, program, feed, wire=None, decode_eager=False):
        vals = {}
        gb = program.global_block()
        for name, value in feed.items():
            var = gb.vars.get(name)
            tv = executor_core.feed_to_tracevalue(value, var)
            wired = wire is not None and name in wire \
                and not isinstance(tv, SeqTensor)
            if wired and decode_eager:
                # eager (host-op) programs have no compiled step to fuse
                # the decode into; decode at feed time instead
                tv = wire[name].decode(
                    tv, var.dtype if var is not None else None)
                wired = False
            if var is not None and not isinstance(tv, SeqTensor) \
                    and not wired:
                want = var.dtype
                if str(tv.dtype) != want and want is not None:
                    tv = tv.astype(want)
            vals[name] = tv
        return vals

    def _rng_for(self, program, iters=None):
        """The next step's rng (the next `iters` steps' for a scan) of the
        program's own stream; the counter is the program's step number
        (trainer.py restores it with a checkpoint)."""
        key = id(program)
        step0 = self._step_counter.get(key, 0)
        self._step_counter[key] = step0 + (1 if iters is None else iters)
        return step_rng(program, step0, iters)

    # ------------------------------------------------------------------
    def _run_compiled(self, program, scope, feed, fetch_names, use_cache,
                      iters=None, wire=None, donate_feeds=False, mon=None):
        feed_vals = self._feed_values(program, feed, wire=wire) \
            if iters is None \
            else stack_multi_step_feeds(program, feed, iters, wire=wire)
        if mon is not None:
            mon.lap("feed_encode")
        step = prepare_step(
            self._compile_cache, program, scope, feed_vals, fetch_names,
            iters=iters, wire=wire, donate_feeds=donate_feeds, mon=mon,
            kind="executor", devices=[jax_device_for(self.place)],
            l2_extra=self._l2_extra, use_cache=use_cache)
        mut_state, const_state = split_state(step)
        step0 = self._step_counter.get(id(program), 0)
        rng = self._rng_for(program, iters)
        t0 = time.perf_counter() \
            if iters is None and flags.get("benchmark") else None
        if mon is not None:
            mon.lap("state_gather")
        fetches, new_mut = step.compiled(mut_state, const_state, feed_vals,
                                         rng)
        fetches = finish_step(step, fetches, new_mut, step0)
        if t0 is not None:
            self._report_benchmark(t0, fetches, new_mut)
        if flags.get("check_nan_inf"):
            # per-op blame isn't available inside one XLA computation; check
            # the step boundary (fetches + updated state) and name the var
            executor_core.check_values_finite(
                list(zip(fetch_names, fetches)) + list(new_mut.items()),
                context=" after compiled step" if iters is None
                else f" after compiled {iters}-step scan")
        return [to_host(f) for f in fetches]

    def _report_benchmark(self, t0, fetches, new_mut):
        """FLAGS_benchmark: synchronize + report."""
        jax.block_until_ready((fetches, new_mut))
        import sys
        # reference FLAGS_benchmark also reports per-op memory
        # (executor.cc:339); XLA owns allocation here, so the
        # equivalent debugging signal is the device's peak-HBM mark
        mem = ""
        try:
            stats = jax_device_for(self.place).memory_stats() or {}
            peak = stats.get("peak_bytes_in_use")
            if peak is not None:
                mem = f" peak_hbm={peak / 1e6:.1f}MB"
        except Exception:
            pass
        # the timing is a metric first, a log line second: record the
        # fenced wall time in the monitor registry and print THAT value
        reg = monitor.registry()
        g = reg.gauge("benchmark_run_ms",
                      help="FLAGS_benchmark fenced wall time per run")
        g.set((time.perf_counter() - t0) * 1000.0)
        reg.histogram("benchmark_run_ms_hist",
                      help="FLAGS_benchmark fenced wall time "
                           "distribution").observe(g.value)
        print(f"[paddle_tpu] run: {g.value:.3f}"
              f" ms (fetches={len(fetches)}){mem}", file=sys.stderr)

    # ------------------------------------------------------------------
    def run_block_eager(self, block, scope):
        """Run one block's ops eagerly against `scope` (reference
        listen_and_serv_op.cc ParallelExecuteBlocks: nested
        Executor::RunPreparedContext on a sub-block)."""
        env = {}
        for n in _block_touched_names(block):
            v = scope.find_var(n)
            if v is not None:
                env[n] = (
                    executor_core.feed_to_tracevalue(v)
                    if isinstance(v, LoDTensor) else v
                )
        ctx = executor_core.OpContext(eager=True, scope=scope,
                                      place=self.place)
        with self._device_scope():
            executor_core.run_ops(block.ops, env, ctx)
        # write back only durable vars (persistable, or already living in
        # the scope) — block-local temporaries like grad.merged stay out
        for op in block.ops:
            for n in op.output_arg_names():
                if n not in env:
                    continue
                var = block.vars.get(n) or block.program.global_block().vars.get(n)
                if (var is not None and var.persistable) or \
                        scope.find_var(n) is not None:
                    scope.var(n)
                    scope.set_var(n, env[n])

    def _run_eager(self, program, scope, feed, fetch_names, wire=None,
                   mon=None):
        feed_vals = self._feed_values(program, feed, wire=wire,
                                      decode_eager=True)
        if mon is not None:
            mon.lap("feed_encode")
        env = {}
        touched = set()
        for b in program.blocks:
            for op in b.ops:
                touched.update(op.input_arg_names())
                touched.update(op.output_arg_names())
        for n in touched:
            v = scope.find_var(n)
            if v is not None:
                env[n] = (
                    executor_core.feed_to_tracevalue(v) if isinstance(v, LoDTensor) else v
                )
        env.update(feed_vals)
        fetch_sink = []
        ctx = executor_core.OpContext(
            rng=self._rng_for(program),
            eager=True,
            scope=scope,
            feed=feed_vals,
            fetch_sink=fetch_sink,
            place=self.place,
        )
        if mon is not None:
            mon.lap("state_gather")
        executor_core.run_ops(program.global_block().ops, env, ctx)
        if mon is not None:
            mon.lap("dispatch")
        persistable = {
            n
            for blk in program.blocks
            for n, v in blk.vars.items()
            if v.persistable
        }
        for n in persistable & set(env.keys()):
            scope.var(n)
            scope.set_var(n, env[n])
        outs = []
        for n in fetch_names:
            outs.append(to_host(executor_core.env_get(env, n)))
        return outs


def _block_touched_names(block):
    names = set()
    for op in block.ops:
        names.update(op.input_arg_names())
        names.update(op.output_arg_names())
    return names
