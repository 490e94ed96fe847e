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
from .framework import OP_NAMESCOPE_ATTR_NAME, OWNER_NAMESCOPE_ATTR_NAME
from .. import amp, flags
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
                 fetch_sink=None, place=None, constraints=None,
                 kept_copies=None):
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
        # {parameter: its kept low-precision copy} (amp.kept_copies): an op
        # that would have the policy cast the master gets the copy
        self.kept_copies = kept_copies or {}

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


def run_ops(ops, env, ctx):
    # offered every op of a traced block, says whether it lowered the op
    pairs = None if ctx.eager else bn_pool.Lowering(
        ops, ctx, _run_one_op, _bind_outputs, _device_scope)
    for op in ops:
        if pairs is None or not pairs.offer(op, env, ctx):
            _run_one_op(op, env, ctx)
    return env


def _device_scope(op, ctx):
    """Every op of a traced step is lowered under jax.named_scope(`<scopes>/
    <op type>`), so each operation of a device trace carries the Fluid op
    it came from, forward and backward (`<type>_grad`): `<scopes>` are the
    `fluid.name_scope`s / `framework.op_scope`s the op was appended under,
    and an op appended under none lowers under its type alone. An op that
    names an owner's scopes (an optimizer's update its parameter's, a
    gradient `sum` those of the op it sums for) carries them inside ONE
    path component, their `/` written `.`: `optimizer/momentum(stage1.
    block0.conv1)`, so a reader that matches whole components finds
    `optimizer` and finds neither `stage1` nor `block0`. Named scopes are
    metadata: the lowered StableHLO is the same
    text, and so is the persistent cache's key."""
    if ctx.eager:
        return contextlib.nullcontext()
    scopes = op.attrs.get(OP_NAMESCOPE_ATTR_NAME)
    name = f"{scopes}/{op.type}" if scopes else op.type
    owner = op.attrs.get(OWNER_NAMESCOPE_ATTR_NAME)
    return jax.named_scope(
        f"{name}({owner.replace('/', '.')})" if owner else name)


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
    # the slots in which a parameter's kept copy stands in for the cast
    kept_slots = amp.kernel_slots(op.type) if ctx.kept_copies else ()
    for slot, names in op.inputs.items():
        ins[slot] = [
            None if n == "" else env_get(env, n, allow_missing=lazy)
            for n in names
        ]
        if slot in kept_slots:
            ins[slot] = [amp.kept_copy(v, env.get(ctx.kept_copies.get(n)))
                         for n, v in zip(names, ins[slot])]
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
def refresh_kept_copies(program, scope):
    """Before a step's state is gathered from `scope`: every kept copy the
    step may read (amp.kept_copies) is the cast of the master the step is
    about to be given. The scope remembers which value of the master each
    copy was cast from; a master that is another value now (set_var, a
    load, a restored checkpoint, another program's update) or a copy that
    is not there (a fresh scope) gets the one cast the step would
    otherwise have made. After the step `note_kept_copies` remembers the
    masters it wrote: a training loop pays a dict lookup a copy."""
    kept = amp.kept_copies(program)[0]
    for param, copy in kept.items():
        master = scope.find_var(param)
        if master is None or (scope.cast_from.get(copy) is master
                              and scope.has_var(copy)):
            continue
        dtype = dtypes.to_jnp(program.global_block().vars[copy].dtype)
        scope.set_var(copy, jnp.asarray(master).astype(dtype))
        scope.cast_from[copy] = master if isinstance(master, jax.Array) \
            else None  # a host array can change in place: cast it again


def note_kept_copies(program, scope, new_state):
    """After a step wrote `new_state` back: its update ops wrote master
    and copy together."""
    for param, copy in amp.kept_copies(program)[0].items():
        if param in new_state and copy in new_state:
            scope.cast_from[copy] = new_state[param]


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
    kept = amp.kept_copies(program)[0]

    def step(mut_state, const_state, feeds, rng):
        env = {}
        env.update(const_state)
        env.update(mut_state)
        env.update(feeds)
        ctx = OpContext(rng=rng, is_test=is_test, constraints=constraints,
                        kept_copies=kept)
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


def build_multi_step_fn(step, iters):
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

    def multi(mut_state, const_state, stacked_feeds, rng):
        base_key, step0 = rng

        def body(st, xs):
            i, feeds = xs
            sub = jax.random.fold_in(base_key, step0 + i)
            fetches, new_mut = step(st, const_state, feeds, sub)
            # carry structure must be invariant across iterations: state the
            # step writes replaces the carried entry; state it only reads
            # rides through unchanged. Written-but-never-carried names are
            # rejected up front by the Executor (see run(iters=...)).
            st = {n: new_mut.get(n, v) for n, v in st.items()}
            return st, fetches

        st, fetches = jax.lax.scan(
            body, mut_state,
            (jnp.arange(iters, dtype=jnp.int32), stacked_feeds),
            length=iters)
        return fetches, st

    return multi


def step_key(program, feed_vals, fetch_names, state_names, *, iters=None,
             wire=None, donate_feeds=False, health=None, extra=()):
    """The compile key of one step, as (identity, content).

    identity pins the program within this process: (id, mutation counter).
    content is everything else that changes the traced or compiled step:
    feed shape/dtype specs, fetch and state names, the amp policy,
    debug_nans (it turns state donation off), `iters` (None = the plain
    step, K = the K-step scan), the wire spec, feed donation, the health
    plan, and the caller's `extra` entries (ParallelExecutor: zero1 /
    overlap / autoshard / pipeline). It is built from sorted tuples of
    primitives only, so CompileCache.load_or_build can digest it as it is.

    The in-memory (L1) key is identity + content. A trace-affecting input
    that is not in here is a silently reused executable: add it here, and
    a case to tests/test_compile_cache.py's ingredient test."""
    content = (
        tuple(sorted((n, spec_of(v)) for n, v in feed_vals.items())),
        tuple(fetch_names),
        tuple(state_names),
        amp.fingerprint(),
        flags.get("debug_nans"),
        ("iters", iters),
        ("wire", wire.fingerprint() if wire is not None else None),
        ("donate_feeds", donate_feeds),
        ("health", health.digest if health is not None else None),
    ) + tuple(extra)
    return (id(program), program._mutation), content


# the names of step_key's content, in its order; the caller's `extra`
# entries follow them
CONTENT_PARTS = ("feeds", "fetches", "state", "amp", "debug_nans", "iters",
                 "wire", "donate_feeds", "health")


def key_parts(content):
    """{name: part} of a step_key content: what a build record compares
    with its program's last build to say which part changed
    (cache.builds: `key_diff`)."""
    n = len(CONTENT_PARTS)
    return dict(zip(CONTENT_PARTS, content[:n]), extra=content[n:])


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
