"""ParallelExecutor: single-process data parallelism over the TPU mesh.

Reference parity: paddle/fluid/framework/parallel_executor.cc:54 +
python/paddle/fluid/parallel_executor.py. The reference builds an SSA graph
with one NCCL all-reduce per gradient and a threaded dataflow executor
(threaded_ssa_graph_executor.cc:33). TPU-native equivalent: the SAME traced
step function as Executor, jit-compiled over a jax.sharding.Mesh with
  - feeds sharded on the batch axis (P("dp"))
  - parameters/optimizer state replicated (BuildStrategy.AllReduce) or
    sharded on dim0 (BuildStrategy.Reduce — ZeRO-1-style, the analogue of
    the reference's kReduce balancing strategy, multi_devices_graph_builder
    .cc:221)
XLA inserts the gradient all-reduce/reduce-scatter collectives over ICI and
overlaps them with compute — the role the ThreadedSSAGraphExecutor +
allow_op_delay flags played on GPU.

ZeRO-1 sharded weight update (BuildStrategy.sharded_weight_update /
FLAGS_zero1, arXiv 2004.13336): the program is rewritten by
parallel.zero1.apply before compilation — gradients reduce-scatter over
the dp axis, each replica updates a 1/N param shard with shard-sized
optimizer accumulators, updated shards all-gather back into the
replicated param. The all-gather sits at the tail of the traced step with
no same-step consumers, so XLA overlaps it with the next scan iteration's
forward (iters=K) and, on the per-step path, it completes under async
dispatch while the host preps the next feed.

Multi-node ("NCCL2 mode", num_trainers/trainer_id) maps to jax.distributed
with a mesh spanning hosts; see parallel/distributed.py.
"""

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import analysis
from . import flags
from . import monitor
from .cache import CompileCache, place_jax_cache
from .core import executor_core
from .core.framework import Parameter, default_main_program
from .core.lod_tensor import LoDTensor
from .core.places import accelerator_devices
from .core.registry import SeqTensor
from .core.scope import global_scope
from .executor import (begin_step, end_step, finish_step, prepare_step,
                       split_state, stack_multi_step_feeds, step_rng,
                       to_host)
from .parallel import autoshard as _autoshard
from .parallel import zero1 as _zero1
from .resilience import chaos as _chaos
from .resilience import watchdog as _watchdog

__all__ = ["ParallelExecutor", "ExecutionStrategy", "BuildStrategy"]


class ExecutionStrategy:
    """reference framework/details/execution_strategy.h. On TPU these are
    advisory: XLA owns scheduling. Kept for API parity + cache control."""

    def __init__(self):
        self.num_threads = 0
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 100
        self.use_event = True


class BuildStrategy:
    """reference framework/details/build_strategy.h:22-31."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1  # -> shard optimizer state over the mesh (ZeRO-1 analogue)

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        # ZeRO-1 sharded weight update (arXiv 2004.13336): None defers to
        # FLAGS_zero1; True/False overrides the flag for this executor
        self.sharded_weight_update = None
        # GSPMD-style autoshard (parallel.autoshard): propagate set_sharding
        # seeds over the whole program and lower the plan as
        # with_sharding_constraint. None defers to FLAGS_autoshard.
        self.auto_sharding = None
        self.debug_graphviz_path = ""


class ParallelExecutor:
    def __init__(
        self,
        use_cuda=True,
        loss_name=None,
        main_program=None,
        share_vars_from=None,
        exec_strategy=None,
        build_strategy=None,
        num_trainers=1,
        trainer_id=0,
        use_tpu=None,
        mesh_shape=None,
        devices=None,
        **kwargs,
    ):
        self._program = main_program or default_main_program()
        self._loss_name = loss_name
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._build_strategy = build_strategy or BuildStrategy()
        self._scope = (
            share_vars_from._scope if share_vars_from is not None else global_scope()
        )
        accel = use_tpu if use_tpu is not None else use_cuda
        if devices is not None:
            # explicit device subset — the elastic resize path re-forms a
            # smaller mesh over the survivors' device slots
            accel_devs = list(devices)
        elif accel:
            # same rule as TPUPlace: the accelerators, or the host devices
            # under an explicit CPU pin — never a silent CPU stand-in
            accel_devs = accelerator_devices(jax.devices())
        else:
            accel_devs = jax.devices()
        self._devices = accel_devs
        if mesh_shape:
            # user-declared multi-axis mesh ({"dp": 2, "mp": 4}); variables
            # annotated via parallel.set_sharding place onto these axes
            axes = list(mesh_shape.items())
            total = int(np.prod([s for _, s in axes]))
            if total != len(self._devices):
                raise ValueError(
                    f"mesh_shape {mesh_shape} needs {total} devices, have "
                    f"{len(self._devices)}")
            self._mesh = Mesh(
                np.array(self._devices).reshape([s for _, s in axes]),
                tuple(n for n, _ in axes))
        else:
            self._mesh = Mesh(np.array(self._devices), ("dp",))
        place_jax_cache()
        self._compile_cache = CompileCache("parallel_executor")
        # zero1/grad-scale rewritten program clones, keyed on the source
        # program identity + mutation counter; strong refs keep id() stable
        # for the compile cache
        self._rewrite_cache = {}
        # autoshard ShardingPlans, keyed on (program identity, mutation)
        self._autoshard_cache = {}
        # overlap-scheduled (reordered) clones of the resolved program +
        # their ScheduleReport, keyed on (program identity, mutation,
        # bucket bytes); strong refs keep id() stable for the compile cache
        self._overlap_cache = {}
        self._step = 0
        self.num_trainers = num_trainers
        self.trainer_id = trainer_id

    @property
    def device_count(self):
        return len(self._devices)

    def compile_cache_info(self):
        """Compile-cache stats: entries plus hit/miss/eviction counters and
        the persistent-L2 counter family (cache.CompileCache.info). The
        "entries" key is load-bearing — the serving engine diffs it across
        warmup to assert zero steady-state compiles."""
        return self._compile_cache.info()

    def _l2_extra(self):
        """Mesh/device context folded into the persistent-cache digest: a
        serialized executable is bound to its device assignment, so an
        elastic resize (different mesh geometry or device set) takes a
        clean miss instead of a deserialize-time failure."""
        return (
            ("mesh", tuple((str(k), int(v))
                           for k, v in self._mesh.shape.items())),
            ("devices", tuple(
                (getattr(d, "platform", "?"), int(getattr(d, "id", -1)))
                for d in self._devices)),
            ("procs", int(jax.process_count()), int(jax.process_index())),
        )

    # ------------------------------------------------------------------
    def _prepare_program(self, program, use_zero1, gss, dp_n):
        """Resolve the program actually compiled this run.

        zero1: parallel.zero1.apply clones the program and sandwiches every
        shardable optimizer op between a gradient reduce-scatter and a param
        all-gather, with GradientScaleStrategy folded into the scatter (One
        = sum semantics -> x dp_n; CoeffNumDevice = mean, and the traced
        loss already averages over the GLOBAL batch, so the folded scale is
        1.0). all-reduce path: GradientScaleStrategy.One inserts the
        equivalent full-size per-grad scale ops so the two paths stay
        numerically comparable. Clones are cached per (program identity,
        mutation, zero1, scale strategy, dp size) so recompiles only track
        real program mutations."""
        key = (id(program), program._mutation, use_zero1, gss, dp_n)
        hit = self._rewrite_cache.get(key)
        if hit is not None:
            return hit
        one = BuildStrategy.GradientScaleStrategy.One
        if use_zero1:
            run_program, plan = _zero1.apply(
                program, dp_n,
                grad_scale=float(dp_n) if gss == one else 1.0)
            if not plan.entries:
                # nothing shardable: keep the original so the compile cache
                # is shared with the plain all-reduce path
                run_program = program
        else:
            plan = _zero1.build_plan(program, dp_n)
            run_program = program
            if gss == one and plan.entries:
                run_program = _zero1.apply_grad_scale(
                    program, plan, float(dp_n))
        self._rewrite_cache[key] = (run_program, plan)
        return run_program, plan

    def _overlap_program(self, program, feed_names=None):
        """Apply the static overlap schedule (analysis.schedule) to the
        RESOLVED program: hoist the legal zero1_scatter reduce-scatters
        into the backward section, bucketed under
        FLAGS_overlap_bucket_bytes. Returns (program', ScheduleReport);
        cached per (program identity, mutation, bucket bytes). A program
        carrying any PTA03x dataflow hazard raises
        ProgramVerificationError — it is never silently reordered."""
        key = (id(program), program._mutation,
               int(flags.get("overlap_bucket_bytes")))
        hit = self._overlap_cache.get(key)
        if hit is None:
            sched = analysis.schedule.analyze(
                program,
                mesh_axes={str(k): int(v)
                           for k, v in self._mesh.shape.items()},
                feed_names=feed_names)
            reordered, _ = analysis.schedule.apply_plan(
                program, sched.plan, feed_names=feed_names)
            hit = (reordered, sched)
            self._overlap_cache[key] = hit
        return hit

    def _autoshard_plan(self, program):
        """Total ShardingPlan for the RESOLVED program (zero1-rewritten when
        that pass is on, so its shard-layout accumulator annotations become
        locked seeds). Cached per (program identity, mutation, mesh)."""
        mesh_axes = {str(k): int(v) for k, v in self._mesh.shape.items()}
        key = (id(program), program._mutation,
               tuple(sorted(mesh_axes.items())))
        plan = self._autoshard_cache.get(key)
        if plan is None:
            plan = _autoshard.build_plan(program, mesh_axes)
            self._autoshard_cache[key] = plan
        return plan

    def _state_sharding(self, name, value, program=None, plan=None):
        """User set_sharding() rules win; then the autoshard plan's spec
        when a plan is active; else replicated by default, with
        BuildStrategy.Reduce sharding optimizer accumulators (non-Parameter
        persistables) on dim 0 when divisible (ZeRO-1 analogue)."""
        program = program if program is not None else self._program
        var = program.global_block().vars.get(name)
        spec = getattr(var, "sharding", None) if var is not None else None
        if spec is not None:
            ndim = len(value.shape) if hasattr(value, "shape") else 0
            if len(spec) > ndim:
                raise ValueError(
                    f"{name}: sharding spec {spec} longer than the runtime "
                    f"rank {ndim}")
            for d, ax in enumerate(spec):
                if ax is None:
                    continue
                if ax not in self._mesh.shape:
                    raise ValueError(
                        f"{name}: sharding axis {ax!r} not in the mesh "
                        f"{dict(self._mesh.shape)} — pass mesh_shape= to "
                        f"ParallelExecutor")
                if value.shape[d] % self._mesh.shape[ax] != 0:
                    raise ValueError(
                        f"{name} dim {d} ({value.shape[d]}) not divisible "
                        f"by mesh axis {ax!r} ({self._mesh.shape[ax]})")
            return NamedSharding(self._mesh, P(*spec))
        if plan is not None:
            pspec = plan.spec_of(name)
            if pspec and hasattr(value, "shape") \
                    and len(pspec) <= len(value.shape):
                # plan specs are derived from static shapes; skip any that
                # don't divide the runtime shape rather than erroring
                ok = all(
                    ax is None or value.shape[d] % self._mesh.shape[ax] == 0
                    for d, ax in enumerate(pspec))
                if ok:
                    return NamedSharding(self._mesh, P(*pspec))
        n = len(self._devices)
        if (
            self._build_strategy.reduce_strategy == BuildStrategy.ReduceStrategy.Reduce
            and not isinstance(var, Parameter)
            and hasattr(value, "shape")
            and value.ndim >= 1
            and value.shape[0] % n == 0
            and value.shape[0] >= n
        ):
            return NamedSharding(self._mesh, P("dp"))
        return NamedSharding(self._mesh, P())

    def _feed_sharding(self, value, leading_steps=False):
        if isinstance(value, SeqTensor):
            return SeqTensor(
                jax.device_put(value.data, NamedSharding(self._mesh, P("dp"))),
                jax.device_put(value.lengths, NamedSharding(self._mesh, P("dp"))),
            )
        # iters=K feeds carry a leading [K] step axis; the batch axis to
        # shard over dp is axis 1 there
        spec = P(None, "dp") if leading_steps else P("dp")
        return jax.device_put(value, NamedSharding(self._mesh, spec))

    @property
    def _dp_size(self):
        return int(dict(self._mesh.shape).get("dp", 1))

    def _resolve_program(self, feed, mon):
        """The program this run compiles and the plans that shaped it:
        (program, zplan, osched, aplan, use_zero1). Brings the scope's
        accumulators into the layout the program expects, sets the
        monitor's gauges of each plan, and closes a `cache_lookup` lap."""
        program, scope = self._program, self._scope
        monitored = mon is not None and mon.monitored
        bs = self._build_strategy
        use_zero1 = bs.sharded_weight_update
        if use_zero1 is None:
            use_zero1 = bool(flags.get("zero1"))
        dp_n = self._dp_size
        use_zero1 = bool(use_zero1) and dp_n >= 2
        gss = bs.gradient_scale_strategy
        # everything below (feed staging, state collection, trace, state
        # placement) runs against the resolved program — the zero1 rewrite
        # when sharding is on, else the original (plus One-scale ops)
        program, zplan = self._prepare_program(program, use_zero1, gss, dp_n)
        # static overlap schedule (FLAGS_overlap_plan): reorder the zero1-
        # rewritten program so grad reduce-scatters overlap the backward
        # pass. Hazard-checked, cached, and compile-cache-keyed below.
        use_overlap = bool(flags.get("overlap_plan")) and use_zero1 \
            and bool(zplan.entries)
        osched = None
        if use_overlap:
            program, osched = self._overlap_program(
                program,
                feed_names=list(feed) if isinstance(feed, dict) else None)
        use_autoshard = bs.auto_sharding
        if use_autoshard is None:
            use_autoshard = bool(flags.get("autoshard"))
        use_autoshard = bool(use_autoshard) and len(self._devices) > 1
        aplan = None
        if use_autoshard:
            # built on the RESOLVED program so zero1's accumulator layouts
            # compose as locked seeds; raises the clear compile-time error
            # for bad seeds (unknown axis / non-divisible static dim)
            aplan = self._autoshard_plan(program)
            _autoshard.register_plan(aplan)
        else:
            # same compile-time seed validation even when the pass is off —
            # a bad annotation should never surface mid-placement
            _autoshard.validate_seeds(program, dict(self._mesh.shape))
        if use_zero1 and zplan.entries:
            # accumulators live permanently in [dp_n, shard] layout; a
            # full-layout scope (startup init, or a checkpoint restore)
            # converts once here
            zplan.ensure_scope_sharded(scope)
        else:
            # restore onto zero1=0 after a sharded run: fold any shard-
            # layout accumulators back to their canonical full layout
            _zero1.ensure_scope_unsharded(scope, program)
        if monitored and zplan.entries:
            # analytic ring-collective accounting for the dp gradient path
            # (bytes, not time — XLA owns the schedule); journal extras ride
            # into the JSONL record for `python -m paddle_tpu monitor`
            cb = zplan.collective_bytes(sharded=use_zero1)
            osb = zplan.optimizer_state_bytes(sharded=use_zero1)
            reg = monitor.registry()
            for op_name, nbytes in sorted(cb.items()):
                reg.gauge(
                    "collective_bytes_per_step",
                    help="analytic per-step dp-collective traffic (ring)",
                    op=op_name).set(float(nbytes))
            reg.gauge(
                "optimizer_state_bytes_per_replica",
                help="optimizer accumulator bytes resident per replica",
            ).set(float(osb))
            if mon.extra is None:
                mon.extra = {}
            mon.extra["collective_bytes"] = {
                k: int(v) for k, v in cb.items()}
            mon.extra["optimizer_state_bytes"] = int(osb)
            mon.extra["zero1"] = bool(use_zero1)
        if monitored and osched is not None:
            analysis.schedule.record_gauges(
                osched, context="parallel_executor")
            if mon.extra is None:
                mon.extra = {}
            mon.extra["overlap"] = {
                "critical_path_ms": float(osched.critical_path_ms),
                "hoistable_bytes": int(osched.plan.hoistable_bytes),
                "buckets": len(osched.plan.buckets),
                "moves": len(osched.plan.moves),
                "digest": osched.plan.digest(),
            }
        if monitored and aplan is not None:
            reg = monitor.registry()
            reg.gauge(
                "autoshard_reshard_bytes_per_step",
                help="analytic per-step reshard traffic forced by plan "
                     "conflicts and locked-seed boundaries",
            ).set(float(aplan.reshard_bytes_per_step()))
            reg.gauge(
                "autoshard_plan_vars",
                help="variables covered by the active autoshard plan",
            ).set(float(len(aplan.specs)))
            reg.gauge(
                "autoshard_plan_sharded_vars",
                help="plan variables with at least one sharded dim",
            ).set(float(len(aplan.sharded_names())))
            reg.gauge(
                "autoshard_conflicts_resolved",
                help="propagation conflicts arbitrated by the cost model",
            ).set(float(len(aplan.conflicts)))
            reg.gauge(
                "autoshard_unresolved_vars",
                help="plan variables with no resolvable layout (should be 0)",
            ).set(float(len(aplan.unresolved)))
            if mon.extra is None:
                mon.extra = {}
            mon.extra["autoshard"] = {
                "digest": aplan.digest(),
                "sharded_vars": len(aplan.sharded_names()),
                "conflicts": len(aplan.conflicts),
                "reshard_bytes": int(aplan.reshard_bytes_per_step()),
            }
        if mon is not None:
            # program resolution: zero1 / overlap / autoshard plans
            # (memoized per program) and their digests for the cache key
            mon.lap("cache_lookup")
        return program, zplan, osched, aplan, use_zero1

    # ------------------------------------------------------------------
    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True,
            iters=None, async_fetch=False, donate_feeds=None):
        """One data-parallel step over the mesh — or, with `iters=K`, K
        steps inside ONE jit'd lax.scan dispatch (feeds carry a leading
        [K] axis, batch sharded over "dp" on axis 1; fetches come back
        stacked [K, ...]). Same contract as Executor.run(iters=K).

        `feed` may be a datapipe.DataPipe: the next prefetched chunk is
        pulled here (the step's `feed_wait` phase; DataPipeError from a
        dead decode worker propagates) and iters defaults to the pipe's
        chunk size. Transfer-engine markers (WIRE_KEY/DONATE_KEY) riding
        a staged chunk are honoured the same way as Executor.run: wire
        decode fused into the compiled step, single-use chunks donated —
        including chunks staged zero-copy from the process-pool shm ring.
        `async_fetch=True`
        returns FetchFuture handles instead of host arrays."""
        mon, pipe, feed, iters, wire, donate_feeds, fetch_names = \
            begin_step("parallel_executor",
                       feed if feed is not None else feed_dict, iters,
                       donate_feeds, fetch_list)
        if isinstance(feed, list) and iters is None:
            # per-device feed list (reference feed_parallel): concatenate
            merged = {}
            for d in feed:
                for k, v in d.items():
                    arr = np.asarray(v.numpy() if isinstance(v, LoDTensor) else v)
                    merged.setdefault(k, []).append(arr)
            feed = {k: np.concatenate(vs, axis=0) for k, vs in merged.items()}
        scope = self._scope
        program, zplan, osched, aplan, use_zero1 = \
            self._resolve_program(feed, mon)
        feed_vals = {}
        if iters is not None:
            # shared stacking helper: list-length and leading-axis checks,
            # LoD rejection, dtype cast — the same contract as
            # Executor.run(iters=K); an empty feed list fails there too
            for name, value in stack_multi_step_feeds(
                    program, feed if feed is not None else {},
                    iters, wire=wire).items():
                feed_vals[name] = self._feed_sharding(
                    value, leading_steps=True)
        else:
            for name, value in (feed or {}).items():
                tv = executor_core.feed_to_tracevalue(value)
                feed_vals[name] = self._feed_sharding(tv)
        if mon is not None:
            # stacking + device_put onto the mesh (the h2d link for feeds)
            mon.lap("feed_encode")

        step = prepare_step(
            self._compile_cache, program, scope, feed_vals, fetch_names,
            iters=iters, wire=wire, donate_feeds=donate_feeds, mon=mon,
            kind="parallel_executor", devices=self._devices,
            l2_extra=self._l2_extra,
            key_extra=(
                ("zero1", use_zero1,
                 self._build_strategy.gradient_scale_strategy,
                 self._dp_size),
                ("overlap",
                 osched.plan.digest() if osched is not None else None),
                ("autoshard", aplan.digest() if aplan is not None else None),
                # stage programs from parallel.pipeline share var names
                # with each other and the source program; the (plan digest,
                # stage, phase) tag keeps their executables from colliding
                ("pipeline", getattr(program, "_pipeline_stage", None)),
            ),
            # with the mesh and the zero1/autoshard plans in scope the
            # `full` level of FLAGS_verify can run the sharding checks and
            # the per-replica peak-HBM estimate
            verify=dict(
                mesh_axes=dict(self._mesh.shape),
                zplan=zplan if use_zero1 and zplan.entries else None,
                aplan=aplan),
            constraints=None if aplan is None else lambda: {
                n: NamedSharding(self._mesh, P(*s))
                for n, s in aplan.boundary_specs().items()})
        mut_state, const_state = split_state(
            step, place=self._state_placer(program, aplan))

        step0 = self._step
        self._step += 1 if iters is None else iters
        rng = step_rng(program, step0, iters)
        # fault-injection hook (no-op without an installed ChaosMonkey),
        # before the dispatch so donated buffers are intact on a raise
        _chaos.on_run("parallel_executor")
        if mon is not None:
            # scope reads and (first step only) placement onto the mesh
            mon.lap("state_gather")
        with _watchdog.armed("parallel_executor"), self._mesh:
            fetches, new_mut = step.compiled(mut_state, const_state,
                                             feed_vals, rng)
        replica = {}
        if mon is not None and mon.monitored \
                and flags.get("monitor_replica_skew"):
            # fence each replica's shard of a step output in device
            # order — stamps per-replica completion. Synchronizes the
            # dispatch queue, hence the separate opt-in flag.
            leaf = fetches[0] if fetch_names else \
                next(iter(new_mut.values()), None)
            if leaf is not None:
                # t_lap: the stamp the call of `compiled` started at
                res = monitor.measure_replica_ms(leaf, mon.t_lap)
                if res is not None:
                    replica = dict(replica_ms=res[0], replica_ids=res[1])
        fetches = finish_step(step, fetches, new_mut, step0)
        if step.was_miss and flags.get("verify") == "full":
            # measured counterpart of the analysis_peak_hbm gauge: bytes
            # actually resident on one device for this step's state (the
            # estimate is gated against this within 2x in the tests)
            live = analysis.measured_live_bytes(
                list(new_mut.values()) + list(const_state.values())
                + list(fetches))
            monitor.registry().gauge(
                "hbm_live_bytes_per_replica",
                help="measured per-device resident bytes of the step "
                     "state + fetches",
            ).set(float(live))
        # the donated inputs die here and not at the return, where no phase
        # would see it: some 500 arrays of four shards each are 1.8 ms
        del mut_state, const_state, feed_vals, new_mut
        return end_step(mon, [to_host(f) for f in fetches], pipe, iters,
                        async_fetch, return_numpy, **replica)

    def _state_placer(self, program, aplan):
        """split_state's `place`: a state value as this mesh must see it."""
        multiproc = any(
            d.process_index != jax.process_index()
            for d in self._mesh.devices.flat)

        def put(v, desired):
            arr = jax.numpy.asarray(v)
            if multiproc:
                # a committed single-device array cannot be resharded onto a
                # cross-process mesh directly; round-trip through the host —
                # every process holds the identical global value (same-seed
                # startup), so device_put scatters consistent local shards
                arr = np.asarray(arr)
            return jax.device_put(arr, desired)

        def place(n, v):
            var = program.global_block().vars.get(n)
            annotated = getattr(var, "sharding", None) is not None
            planned = aplan is not None and bool(aplan.spec_of(n))
            cur = getattr(v, "sharding", None)
            on_mesh = isinstance(cur, NamedSharding) and cur.mesh == self._mesh
            if annotated or planned:
                # the rule (user seed or plan spec) must win over whatever
                # placement startup left behind — but once the array already
                # carries the desired NamedSharding (every step after the
                # first), re-placing would all-gather the shards to host
                desired = self._state_sharding(n, v, program=program,
                                               plan=aplan)
                if cur != desired:
                    v = put(v, desired)
            elif not on_mesh or not getattr(v, "committed", True):
                # startup leaves single-device committed arrays; a jit over
                # the mesh auto-transfers those in-process but REJECTS them
                # when the mesh spans processes — re-place onto this mesh
                v = put(v, self._state_sharding(n, v, program=program,
                                                plan=aplan))
            return v

        return place

    def bcast_params(self):
        """reference parallel_executor.py:242 — under SPMD params live as
        replicated jax.Arrays, so broadcast is placement, done in run()."""
        return None
