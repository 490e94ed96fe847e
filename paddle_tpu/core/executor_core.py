"""Executor core: lowers a Program block to ONE jit-compiled XLA computation.

Reference contrast: paddle/fluid/framework/executor.cc:133 interprets the op
list one kernel launch at a time with a stream sync per run (executor.cc:353).
On TPU the idiomatic execution model is trace-once/compile-once: the whole
block — forward, backward, optimizer ops — becomes a single pure function
    step(state, feeds, rng) -> (fetches, new_state)
jit-compiled by XLA with donated state buffers, so parameters never leave the
device and XLA fuses/schedules everything (its ThreadedSSAGraphExecutor
equivalent is the XLA scheduler itself).

An eager interpret mode (`run_ops_eager`) remains for host-side programs
(save/load/print/readers) — the analogue of the reference's op-by-op path.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from . import registry
from .registry import SeqTensor
from . import dtypes
from .framework import OP_NAMESCOPE_ATTR_NAME
from .. import flags
from ..ops import bn_pool, lm_ops


def check_values_finite(named_values, context=""):
    """FLAGS_check_nan_inf (reference executor.cc:343 CheckTensorNANOrInf):
    raise naming the first variable containing NaN/Inf."""
    from .selected_rows import SelectedRows

    for name, v in named_values:
        if isinstance(v, SeqTensor):
            v = v.data
        elif isinstance(v, SelectedRows):
            v = v.values
        if not hasattr(v, "dtype") or not hasattr(v, "shape"):
            continue
        try:
            kind = np.dtype(v.dtype).kind
        except TypeError:
            kind = "f" if str(v.dtype) == "bfloat16" else "O"
        if kind != "f" and str(v.dtype) != "bfloat16":
            continue
        arr = np.asarray(v, dtype=np.float32) \
            if str(v.dtype) == "bfloat16" else np.asarray(v)
        if not np.isfinite(arr).all():
            what = "NaN" if np.isnan(arr).any() else "Inf"
            raise RuntimeError(
                f"Variable {name!r} contains {what}{context} "
                f"(FLAGS_check_nan_inf)")


class TraceUnsupported(Exception):
    """Raised when a block contains host-only ops and must run eagerly."""


class OpContext:
    """Per-trace context passed to kernels: RNG threading, sub-block
    execution (control flow), test-mode flag."""

    def __init__(self, rng=None, is_test=False, eager=False, scope=None, feed=None,
                 fetch_sink=None, place=None, constraints=None):
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.is_test = is_test
        self.eager = eager
        self.scope = scope  # only in eager mode (host ops need it)
        self.feed = feed or {}
        self.fetch_sink = fetch_sink if fetch_sink is not None else []
        self.place = place
        # {var name: jax.sharding.NamedSharding} — autoshard plan boundaries
        # lowered as with_sharding_constraint at the producing op's output
        # (trace mode only; eager/host ops never see device layouts)
        self.constraints = constraints or {}

    def next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def run_block(self, block, env):
        """Execute a sub-block's ops against `env` (control-flow ops)."""
        run_ops(block.ops, env, self)
        return env


def _profiler_enabled():
    from .. import profiler

    return profiler._enabled


def env_get(env, name, allow_missing=False):
    if name in env:
        return env[name]
    if allow_missing:
        return None
    raise KeyError(f"Variable {name!r} not materialized (missing feed or init?)")


_FUSABLE_OPT = {"sgd", "momentum"}
# Only small parameters are worth batching: their update kernels are
# launch-overhead-bound (ResNet-50's ~106 BN scales/biases measured ~65 us
# each for <10 us of memory traffic), while large tensors are already
# bandwidth-efficient and fusing them breaks XLA's in-place donation
# aliasing (measured 2x slower when everything was concatenated).
_FUSE_MAX_NUMEL = 1 << 18


def _fuse_optimizer_group(ops, start, env, ctx, fused_ids):
    """Batch all SMALL same-type/same-attrs optimizer updates remaining in
    `ops` into ONE kernel call over concatenated flat parameters.

    The updates are elementwise and independent (each op touches only its
    own Param/Velocity), so gathering them from anywhere in the tail of
    the op list is order-safe; all their Grad inputs exist by the time the
    first optimizer op runs (the optimization pass appends them after the
    whole backward). Numerically identical to the per-op path.

    Returns the set of fused op ids (empty when no fusion applies).
    """
    first_op = ops[start]

    def key_attrs(op):
        # op_role / op_role_var markers differ per parameter and don't
        # affect the math — ignore them when grouping
        return {k: v for k, v in op.attrs.items()
                if not k.startswith("op_")}

    a0 = key_attrs(first_op)
    lr_name = (first_op.inputs.get("LearningRate") or [None])[0]
    slots = [s for s in first_op.inputs if s != "LearningRate"]
    group, per_op_ins = [], []
    # Hazards vs ops between `start` and the candidate that do NOT join the
    # group (the fused kernel runs at the first member's position):
    #  - RAW: a member whose input is (re)written by an intervening op
    #    would read a stale value inside the fused call;
    #  - WAR: an intervening op that READS a name the member writes would
    #    observe the post-update value (the fused call commits early).
    # Either way the candidate stays on the per-op path.
    written_between, read_between = set(), set()

    def skip(op):
        written_between.update(op.output_arg_names())
        read_between.update(op.input_arg_names())

    for op in ops[start:]:
        if id(op) in fused_ids or op.type != first_op.type:
            skip(op)
            continue
        if key_attrs(op) != a0 or \
                (op.inputs.get("LearningRate") or [None])[0] != lr_name:
            skip(op)
            continue
        if any(n in written_between for n in op.input_arg_names()) or \
                any(n in read_between for n in op.output_arg_names()):
            skip(op)
            continue
        ins = {}
        ok = True
        for s in op.inputs:
            vals = [env_get(env, n, allow_missing=True)
                    for n in op.inputs[s]]
            ins[s] = vals
            if s == "LearningRate":
                continue
            for v in vals:
                if v is None or isinstance(v, SeqTensor) \
                        or not hasattr(v, "reshape") \
                        or not hasattr(v, "dtype"):
                    ok = False  # SelectedRows/ragged/missing: per-op path
        if not ok:
            skip(op)
            continue
        if int(np.prod(ins["Param"][0].shape)) > _FUSE_MAX_NUMEL:
            skip(op)
            continue
        group.append(op)
        per_op_ins.append(ins)
        # members write too (Param/accumulators): a later candidate reading
        # one of these (same Param updated twice) must stay per-op — inside
        # the fused call it would read the pre-update value
        written_between.update(op.output_arg_names())
    if len(group) < 2:
        return set()
    # RAW dtype homogeneity per slot: run_kernel's amp policy then applies
    # one cast to the concatenated slot, identical to per-op policy casts
    for s in slots:
        d0 = per_op_ins[0][s][0].dtype
        if any(o[s][0].dtype != d0 for o in per_op_ins):
            return set()

    op_def = registry.lookup(first_op.type)
    shapes = [o["Param"][0].shape for o in per_op_ins]
    sizes = [int(np.prod(s)) for s in shapes]
    cat_ins = {
        s: [jnp.concatenate([o[s][0].reshape(-1) for o in per_op_ins])]
        for s in slots
    }
    cat_ins["LearningRate"] = [env_get(env, lr_name)]
    # through run_kernel, not op_def.fn: amp policy + op-coverage tracking
    # apply to the fused call exactly like a per-op call
    outs = registry.run_kernel(op_def, ctx, cat_ins, first_op.attrs) or {}
    offsets = np.cumsum([0] + sizes)
    for slot, vals in outs.items():
        flat = vals[0] if isinstance(vals, list) else vals
        for k, op in enumerate(group):
            names = op.outputs.get(slot) or []
            if not names or not names[0]:
                continue
            env[names[0]] = flat[offsets[k]:offsets[k + 1]].reshape(shapes[k])
    return {id(op) for op in group}


def run_ops(ops, env, ctx):
    fused_ids = set()
    # offered every op of a traced block, says whether it lowered the op
    pairs = None if ctx.eager else bn_pool.Lowering(
        ops, ctx, _run_one_op, _bind_outputs)
    for i, op in enumerate(ops):
        if id(op) in fused_ids:
            continue
        if not ctx.eager and op.type in _FUSABLE_OPT \
                and flags.get("fuse_optimizer_ops"):
            done = _fuse_optimizer_group(ops, i, env, ctx, fused_ids)
            if done:
                fused_ids |= done
                if id(op) in fused_ids:
                    continue
        if pairs is None or not pairs.offer(op, env, ctx):
            _run_one_op(op, env, ctx)
    return env


def _device_scope(op, ctx):
    """An op appended under `fluid.name_scope` / `framework.op_scope` is
    lowered under jax.named_scope(`<scopes>/<op type>`), so the operations
    of a device trace carry the Fluid op they came from, forward and
    backward (`<type>_grad`). Named scopes are metadata: the lowered
    StableHLO is the same text."""
    scopes = op.attrs.get(OP_NAMESCOPE_ATTR_NAME)
    if ctx.eager or not scopes:
        return contextlib.nullcontext()
    return jax.named_scope(f"{scopes}/{op.type}")


def lowered_counts(program, device):
    """{counter: n} of what `run_ops` lowers specially in a step of
    `program` compiled for `device`, for the step spans and the registry
    (monitor.StepRecord.mark_cache): `fused_bn_global_pool` always, the
    language-model lowerings where the program has such ops."""
    return {"fused_bn_global_pool": bn_pool.count(program),
            **lm_ops.lowered_counts(program, device)}


def _run_one_op(op, env, ctx, attrs=None):
    attrs = op.attrs if attrs is None else attrs
    op_def = registry.lookup(op.type)
    if op_def.no_trace and not ctx.eager:
        raise TraceUnsupported(op.type)
    # control-flow / host ops need the op desc + live env (sub-block wiring)
    ctx.current_op = op
    ctx.env = env
    ins = {}
    # declaration-only inputs (e.g. listen_and_serv's recv buffers) are
    # resolved lazily by the kernel itself
    lazy = getattr(op_def, "lazy_inputs", False)
    for slot, names in op.inputs.items():
        ins[slot] = [
            None if n == "" else env_get(env, n, allow_missing=lazy)
            for n in names
        ]
    try:
        if ctx.eager and _profiler_enabled():
            from .. import profiler
            with profiler.record_event(f"op::{op.type}"):
                outs = registry.run_kernel(op_def, ctx, ins, attrs) or {}
        else:
            with _device_scope(op, ctx):
                outs = registry.run_kernel(op_def, ctx, ins, attrs) or {}
    except TraceUnsupported:
        raise
    except Exception as e:
        raise type(e)(f"while running op {op.type!r} ({op!r}): {e}") from e
    if ctx.eager and flags.get("check_nan_inf"):
        named = []
        for slot, names in op.outputs.items():
            vals = outs.get(slot, [])
            for i, n in enumerate(names):
                if n and i < len(vals) and vals[i] is not None:
                    named.append((n, vals[i]))
        check_values_finite(named, context=f" after op {op.type!r}")
    _bind_outputs(op, outs, env, ctx)
    return outs


def _bind_outputs(op, outs, env, ctx):
    cons = ctx.constraints
    for slot, names in op.outputs.items():
        vals = outs.get(slot, [])
        for i, name in enumerate(names):
            if not name:
                continue
            if i < len(vals) and vals[i] is not None:
                v = vals[i]
                if cons and not ctx.eager and name in cons:
                    v = _apply_sharding_constraint(v, cons[name])
                env[name] = v


def _apply_sharding_constraint(v, named_sharding):
    """with_sharding_constraint, skipped for values it can't apply to:
    non-array containers (SeqTensor/SelectedRows), rank shorter than the
    spec, and dims not divisible by their axis sizes (the plan is built
    from static shapes; runtime bucket shapes are authoritative here)."""
    if not hasattr(v, "shape") or not hasattr(v, "dtype") \
            or isinstance(v, SeqTensor):
        return v
    shape = v.shape
    spec = named_sharding.spec
    if len(spec) > len(shape):
        return v
    mesh = named_sharding.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for d, ax in enumerate(spec):
        if ax is None:
            continue
        n = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            n *= sizes.get(a, 1)
        if n and shape[d] % n:
            return v
    return jax.lax.with_sharding_constraint(v, named_sharding)


# ---------------------------------------------------------------------------
# Compiled path
# ---------------------------------------------------------------------------
def collect_state_names(program, scope):
    """Persistable vars the block reads or writes and that exist in scope."""
    gb = program.global_block()
    persistable = {
        n for b in program.blocks for n, v in b.vars.items() if v.persistable
    }
    touched = set()
    for b in program.blocks:
        for op in b.ops:
            touched.update(op.input_arg_names())
            touched.update(op.output_arg_names())
    state_in = sorted(n for n in persistable & touched if scope.has_var(n))
    written = set()
    for b in program.blocks:
        for op in b.ops:
            written.update(set(op.output_arg_names()) & persistable)
    return state_in, sorted(written)


def _block_read_names(op):
    """All var names read anywhere inside an op's sub-blocks (control flow)."""
    names = set()
    for v in op.attrs.values():
        if hasattr(v, "ops"):  # a Block attr
            for sub in v.ops:
                names.update(sub.input_arg_names())
                names.update(_block_read_names(sub))
    return names


def dead_code_eliminate(ops, needed_names):
    """Drop ops whose outputs feed neither fetches nor persistable state.

    The reference relies on Program.prune (framework.py:1112) before
    inference; on the XLA path DCE is the executor's job so a
    clone(for_test=True) program can run with only its data inputs fed.
    Side-effectful host ops are kept conservatively.
    """
    needed = set(needed_names)
    live = []
    for op in reversed(ops):
        outs = set(op.output_arg_names())
        # control-flow ops (any Block attr) write into env by kernel side
        # effect with empty declared outputs — always keep them
        has_sub_block = any(hasattr(v, "ops") for v in op.attrs.values())
        keep = (bool(outs & needed) or has_sub_block
                or op.type in ("print", "assert_op"))
        if keep:
            live.append(op)
            needed |= set(op.input_arg_names())
            needed |= _block_read_names(op)
    live.reverse()
    return live


def build_step_fn(program, fetch_names, state_out_names, is_test=False,
                  constraints=None):
    """Build the pure step function for a program's global block.

    signature: step(mut_state, const_state, feeds, rng) -> (fetches, new_mut)
    mut_state (vars the block writes) is donated by the jit wrapper so
    parameter/optimizer-state buffers are updated in place on device.

    constraints: optional {var name: NamedSharding} applied as
    with_sharding_constraint where each var is produced (autoshard plan
    lowering — see paddle_tpu.parallel.autoshard).
    """
    ops = dead_code_eliminate(
        program.global_block().ops, list(fetch_names) + list(state_out_names)
    )

    def step(mut_state, const_state, feeds, rng):
        env = {}
        env.update(const_state)
        env.update(mut_state)
        env.update(feeds)
        ctx = OpContext(rng=rng, is_test=is_test, constraints=constraints)
        run_ops(ops, env, ctx)
        fetches = [env_get(env, n) for n in fetch_names]
        new_mut = {n: env[n] for n in state_out_names if n in env}
        return fetches, new_mut

    return step


def compile_step_fn(step, donate_state=True, donate_feeds=False,
                    probe=None, aot=None):
    """jit the step. donate_state aliases mut_state so parameters update in
    place; donate_feeds ALSO donates the feeds argument — correct only for
    single-use staged chunks (datapipe transfer engine marks them with
    DONATE_KEY), where it lets XLA reclaim the chunk's staging memory for
    the next transfer instead of holding it to the end of the dispatch.
    Feed buffers rarely alias an output shape, and jax warns at lowering
    about every non-aliasable donated buffer; calls run with that warning
    suppressed (lowering happens on first call, so the jit() site can't
    scope it) because early reuse of the staging memory — not output
    aliasing — is the point of donating feeds.

    probe: optional callable(jitted, args) invoked once immediately before
    the FIRST execution — the only point where the jitted fn and live
    (not-yet-donated) example args coexist, which is what
    monitor.compile_probe needs to lower for HLO cost analysis. Probe
    failures never fail the step.

    aot: optional callable(compiled_executable) — the persistent compile
    cache's export hook. When set, the first call compiles ahead-of-time
    (jit.lower(*args).compile()) instead of priming the lazy jit cache,
    hands the executable to `aot` for serialization, and every later call
    dispatches that executable directly (the lazy cache and the AOT path
    do not share entries, so holding the Compiled is what makes the
    export free). If lowering/AOT compilation fails the call falls back
    to the lazy jit (no export); if a later call's avals drift from the
    AOT signature (jax validates args BEFORE dispatch, so nothing has
    been donated yet) the call retreats to the retracing jit for good."""
    donate = (0,) if donate_state else ()
    if not donate_feeds and probe is None and aot is None:
        return jax.jit(step, donate_argnums=donate)
    compiled = jax.jit(
        step, donate_argnums=donate + ((2,) if donate_feeds else ()))
    probed = [probe is None]
    aot_exe = [None if aot is not None else False]  # False = lazy path

    def call(*args):
        import warnings

        if not probed[0]:
            probed[0] = True
            try:
                probe(compiled, args)
            except Exception:
                pass
        if aot_exe[0] is None:
            try:
                with warnings.catch_warnings():
                    if donate_feeds:
                        warnings.filterwarnings(
                            "ignore",
                            message="Some donated buffers were not usable")
                    exe = compiled.lower(*args).compile()
            except Exception:
                aot_exe[0] = False  # this step can't AOT; stay lazy
            else:
                aot_exe[0] = exe
                try:
                    aot(exe)
                except Exception:
                    pass  # a cache export must never fail the step
        target = aot_exe[0] or compiled
        try:
            if not donate_feeds:
                return target(*args)
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                return target(*args)
        except (TypeError, ValueError):
            if target is compiled:
                raise
            aot_exe[0] = False  # aval drift: the AOT signature is pinned
            return call(*args)

    return call


def collect_ema_states(program, state_out_names, fetch_names=()):
    """{var_name: momentum} for batch-norm running stats that are PURE EMA
    recurrences of this (training) program: written only as a batch_norm's
    MeanOut/VarianceOut, read only as the SAME op's Mean/Variance input,
    and not fetched. These can leave the multi-step scan carry (sparing
    the carry's back-edge copies) and be
    reconstructed exactly after the scan — r_{k+1} = m r_k + (1-m) s_k is a
    linear fold, so r_K = m^K r_0 + Σ m^{K-1-i} (o_i - m r_0) where o_i is
    the step's output against the CONSTANT initial value r_0."""
    candidates = {}
    gb = program.global_block()
    for op in gb.ops:
        if op.type != "batch_norm" or op.attrs.get("is_test", False):
            continue
        momentum = float(op.attrs.get("momentum", 0.9))
        for in_slot, out_slot in (("Mean", "MeanOut"),
                                  ("Variance", "VarianceOut")):
            ins = op.inputs.get(in_slot) or []
            outs = op.outputs.get(out_slot) or []
            if ins and outs and ins[0] == outs[0] and ins[0]:
                candidates[ins[0]] = (momentum, op)
    if not candidates:
        return {}
    fetched = set(fetch_names)
    reads, writes = {}, {}
    for op in gb.ops:
        for n in op.input_arg_names():
            reads.setdefault(n, []).append(op)
        for n in op.output_arg_names():
            writes.setdefault(n, []).append(op)
    out_set = set(state_out_names)
    ema = {}
    for n, (momentum, owner) in candidates.items():
        if n not in out_set or n in fetched:
            continue

        def harmless(o):
            # batch_norm_grad receives the running stats because the
            # default vjp maker forwards every forward input, but its
            # cotangents don't depend on them: MeanOut/VarianceOut are
            # stop-gradient outputs, and the training branch uses BATCH
            # statistics for normalization
            return o is owner or (o.type == "batch_norm_grad"
                                  and not o.attrs.get("is_test", False))

        if any(not harmless(o) for o in reads.get(n, [])):
            continue  # another op consumes the running stat: keep carried
        if any(o is not owner for o in writes.get(n, [])):
            continue
        ema[n] = momentum
    return ema


class PackPlan:
    """Packed small-state storage for the multi-step scan (an experiment
    aimed at the per-parameter update kernels of ResNet-50; see
    FLAGS_pack_small_state for what it showed).

    Instead of carrying each small float parameter/accumulator as its own
    scan-carry leaf (one XLA buffer + back-edge copy + update kernel
    each), all small same-dtype mut-state entries live CONCATENATED in one
    buffer. Inside the step they are sliced back to views (slices fuse
    into the consumers), and the updated values concatenate into the new
    packed buffer — which is the donated carry leaf, so the update lowers
    to (ideally) one fused kernel over one aliased buffer. Contrast with
    r4's rejected concat-fusion, whose slice-back wrote SEPARATE per-param
    output buffers and broke donation aliasing.
    """

    MAX_NUMEL = 1 << 16

    def __init__(self, mut_values, exclude=()):
        by_dtype = {}
        for n in sorted(mut_values):
            v = mut_values[n]
            if n in exclude or isinstance(v, SeqTensor) \
                    or not hasattr(v, "dtype") or not hasattr(v, "shape"):
                continue
            if not jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating):
                continue
            size = int(np.prod(v.shape)) if v.shape else 1
            if size > self.MAX_NUMEL:
                continue
            by_dtype.setdefault(str(v.dtype), []).append(
                (n, size, tuple(v.shape)))
        self.groups = []
        for dtype, entries in sorted(by_dtype.items()):
            if len(entries) < 2:
                continue
            offs, off = [], 0
            for _, size, _ in entries:
                offs.append(off)
                off += size
            self.groups.append(dict(
                key=f"__packed__{dtype}", dtype=dtype, total=off,
                entries=[(n, o, s, shp) for (n, s, shp), o
                         in zip(entries, offs)]))
        self.packed_names = {n for g in self.groups
                             for (n, _, _, _) in g["entries"]}

    @staticmethod
    def pack_group(g, values):
        """One group's members ({name: value}) -> the packed 1-D buffer.
        The single definition of the packed layout's write side."""
        return jnp.concatenate([
            jnp.asarray(values[n]).reshape(-1)
            for n, _, _, _ in g["entries"]])

    @staticmethod
    def group_views(g, P):
        """Packed buffer -> member views, in g["entries"] order. The
        single definition of the packed layout's read side (also what the
        Executor jits for the post-call scope write-back)."""
        return [jax.lax.dynamic_slice(P, (off,), (size,)).reshape(shape)
                for _, off, size, shape in g["entries"]]

    def unpack_into(self, packed_mut):
        """packed mut dict -> {name: view} for every packed member."""
        views = {}
        for g in self.groups:
            for (n, _, _, _), v in zip(
                    g["entries"], self.group_views(g, packed_mut[g["key"]])):
                views[n] = v
        return views

    def wrap_step(self, step):
        """step over individual names -> step over packed mut state."""

        def wrapped(mut_state, const_state, feeds, rng):
            mut = {n: v for n, v in mut_state.items()
                   if not n.startswith("__packed__")}
            views = self.unpack_into(mut_state)
            mut.update(views)
            fetches, new_mut = step(mut, const_state, feeds, rng)
            out = {n: v for n, v in new_mut.items()
                   if n not in self.packed_names}
            for g in self.groups:
                merged = {n: new_mut.get(n, views[n])
                          for n, _, _, _ in g["entries"]}
                out[g["key"]] = self.pack_group(g, merged)
            return fetches, out

        return wrapped


def build_multi_step_fn(step, iters, ema=None):
    """Wrap a step function in a lax.scan over `iters` pre-stacked feeds.

    One XLA dispatch then covers `iters` training steps — the host-loop
    cost of a dispatch and a fetch read-back is paid once per K steps
    instead of once per step. Feeds carry a leading [iters] axis; fetches
    come back stacked the same way.

    signature: multi(mut_state, const_state, stacked_feeds, (base_key, step0))
               -> (stacked_fetches, new_mut)

    Step i draws rng = fold_in(base_key, step0 + i) — the SAME stream the
    sequential per-call path uses (Executor._rng_for), so stochastic
    programs (dropout, random_crop) reproduce K sequential runs exactly.
    step0 must be a traced int32 array (a python int would bake into the
    compiled computation and force a recompile per call).
    """

    ema = ema or {}

    def multi(mut_state, const_state, stacked_feeds, rng):
        base_key, step0 = rng
        # EMA sinks (collect_ema_states) ride OUTSIDE the carry: each step
        # sees the constant initial value r_0 and its per-step output is
        # stacked as a scan Y; the exact K-step fold happens after the scan
        ema_r0 = {n: mut_state[n] for n in ema if n in mut_state}
        carry0 = {n: v for n, v in mut_state.items() if n not in ema_r0}

        def body(st, xs):
            i, feeds = xs
            sub = jax.random.fold_in(base_key, step0 + i)
            full = dict(st)
            full.update(ema_r0)
            fetches, new_mut = step(full, const_state, feeds, sub)
            # carry structure must be invariant across iterations: state the
            # step writes replaces the carried entry; state it only reads
            # rides through unchanged. Written-but-never-carried names are
            # rejected up front by the Executor (see run(iters=...)).
            st = {n: new_mut.get(n, v) for n, v in st.items()}
            ys = {n: new_mut[n] for n in ema_r0 if n in new_mut}
            return st, (fetches, ys)

        st, (fetches, ema_ys) = jax.lax.scan(
            body, carry0,
            (jnp.arange(iters, dtype=jnp.int32), stacked_feeds),
            length=iters)
        # exact reconstruction: o_i = m r_0 + (1-m) s_i was computed against
        # the constant r_0, and the true fold is linear:
        #   r_K = m^K r_0 + Σ_i m^(K-1-i) (o_i - m r_0)
        for n, o_stack in ema_ys.items():
            m = jnp.asarray(ema[n], jnp.float32)
            r0 = ema_r0[n].astype(jnp.float32)
            w = jnp.power(m, jnp.arange(iters - 1, -1, -1, dtype=jnp.float32))
            contrib = jnp.tensordot(
                w, o_stack.astype(jnp.float32) - m * r0[None], axes=1)
            rK = jnp.power(m, iters) * r0 + contrib
            st = dict(st)
            st[n] = rK.astype(ema_r0[n].dtype)
        return fetches, st

    return multi


# ---------------------------------------------------------------------------
# Feed/fetch conversion helpers
# ---------------------------------------------------------------------------
def feed_to_tracevalue(value, var=None):
    """numpy / LoDTensor / jax array -> trace input (array or SeqTensor)."""
    from .lod_tensor import LoDTensor

    if isinstance(value, LoDTensor):
        data = np.asarray(value.numpy())
        if value.lod():
            lengths = np.asarray(
                [b - a for a, b in zip(value.last_level_offsets(), value.last_level_offsets()[1:])],
                dtype=np.int32,
            )
            return SeqTensor(jnp.asarray(data), jnp.asarray(lengths))
        return jnp.asarray(data)
    if isinstance(value, SeqTensor):
        return value
    arr = np.asarray(value)
    return jnp.asarray(arr)


def value_to_lod_tensor(value):
    """trace output -> LoDTensor (host)."""
    from .lod_tensor import LoDTensor

    if isinstance(value, SeqTensor):
        lengths = np.asarray(value.lengths).tolist()
        offsets = [0]
        for l in lengths:
            offsets.append(offsets[-1] + int(l))
        t = LoDTensor(np.asarray(value.data), [offsets])
        return t
    return LoDTensor(np.asarray(value))


def spec_of(value):
    """Hashable signature of a trace input (for the compile cache)."""
    if isinstance(value, SeqTensor):
        return ("seq", tuple(value.data.shape), str(value.data.dtype), tuple(value.lengths.shape))
    return (tuple(np.shape(value)), str(np.asarray(value).dtype) if not hasattr(value, "dtype") else str(value.dtype))
