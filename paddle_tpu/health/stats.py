"""Fused on-device model-health statistics.

When FLAGS_health > 0 the executors extend the step function they are
about to compile: the per-param gradients (already live in the traced
environment — they feed the optimizer ops) are appended to the fetch
list, and `HealthPlan.wrap_step` folds them into ONE compact stats leaf
per parameter inside the jit:

    stats[param] = [sum(g^2), sum(w^2), sum((w_new - w_old)^2),
                    nonfinite(g)]          (float32, shape [4])

so the per-step cost is one reduction per tensor fused into the
already-compiled function — no extra dispatch, no full-tensor readback.

Sharding-awareness falls out of the layout rather than being re-derived
here: under FLAGS_zero1 the optimizer op reads `grad@zero1_rs`, the
reduce-scattered [N, shard] grad whose zero padding makes the shard-local
sum of squares exactly the full grad's, and under autoshard GSPMD lowers
the jnp reductions shard-locally with a tiny combine — stats are computed
on shards and combined, never regathered.

The off path (FLAGS_health == 0) is a single flag check in
`plan_if_enabled`. Host readback, ledger writes, gauges, and detectors
run only every FLAGS_health_interval steps (health/__init__.on_step).
"""

import hashlib

from .. import flags
from ..core.framework import VarType

flags.define("health", int, 0,
             "Model-health telemetry: 0 = off (one flag check per run "
             "call), >0 = fuse per-param grad/weight/update-ratio/"
             "non-finite stats into the compiled step and journal them "
             "to FLAGS_health_ledger every FLAGS_health_interval steps.")
flags.define("health_interval", int, 1,
             "Sample model-health stats every N steps. The reductions "
             "run fused in-graph each step (keeping one trace); readback "
             "+ ledger + detectors fire only on sampled steps.")

# Fields of each per-param stats leaf, in order.
STAT_FIELDS = ("grad_sq", "weight_sq", "delta_sq", "nonfinite")

# zero1.apply rewrites the optimizer op's Param input to the shard-layout
# alias; the canonical (full, persistable) parameter keeps its plain name.
_PARAM_SUFFIXES = ("@zero1_shard",)

_plan_cache = {}  # (id(program), mutation) -> HealthPlan


class HealthPlan:
    """Which (param, grad) pairs a program's step fn collects stats for."""

    __slots__ = ("pairs", "digest")

    def __init__(self, pairs):
        self.pairs = tuple(pairs)  # (label, grad_env_name)
        self.digest = hashlib.sha1(
            repr(self.pairs).encode()).hexdigest()[:12]

    @property
    def fetch_names(self):
        """Grad env names to append to the step fn's fetch list."""
        return [g for _, g in self.pairs]

    def wrap_step(self, step, n_user):
        """Wrap a built step fn: consume the appended grad fetches,
        emit one {label: [4]f32} stats dict as a single extra fetch.

        Applied after the wire wrapper and before the multi-step
        wrapping, so `mut_state`/`new_mut` carry plain var names and the
        scan stacks only the [4]-element leaves, never raw grads.
        """
        import jax.numpy as jnp

        pairs = self.pairs

        def health_step(mut_state, const_state, feeds, rng):
            fetches, new_mut = step(mut_state, const_state, feeds, rng)
            user, grads = fetches[:n_user], fetches[n_user:]
            stats = {}
            for (label, _), g in zip(pairs, grads):
                g32 = jnp.asarray(g).astype(jnp.float32)
                grad_sq = jnp.sum(g32 * g32)
                bad = jnp.sum(
                    (~jnp.isfinite(g32)).astype(jnp.float32))
                w_old = mut_state.get(label)
                if w_old is None:
                    w_old = const_state.get(label)
                w_new = new_mut.get(label)
                if w_new is None:
                    w_new = w_old
                if w_old is not None:
                    wo = jnp.asarray(w_old).astype(jnp.float32)
                    wn = jnp.asarray(w_new).astype(jnp.float32)
                    weight_sq = jnp.sum(wn * wn)
                    d = wn - wo
                    delta_sq = jnp.sum(d * d)
                else:
                    weight_sq = jnp.float32(0.0)
                    delta_sq = jnp.float32(0.0)
                stats[label] = jnp.stack(
                    [grad_sq, weight_sq, delta_sq, bad])
            return list(user) + [stats], new_mut

        return health_step


def plan_for(program):
    """Scan a (resolved) program for optimizer (Param, Grad) pairs.

    Every optimizer op names its inputs through the "Param"/"Grad" slots;
    under FLAGS_zero1 the resolved program carries `p@zero1_shard` /
    `g@zero1_rs` instead — the label strips the shard suffix back to the
    canonical param name (which stays persistable and in mutable state,
    giving the weight-side stats on the full tensor). Sparse
    (SELECTED_ROWS) and ragged grads have no dense norm and are skipped,
    mirroring zero1.build_plan.
    """
    key = (id(program), program._mutation)
    plan = _plan_cache.get(key)
    if plan is not None:
        return plan
    gb = program.global_block()
    pairs, seen = [], set()
    for op in gb.ops:
        pname = (op.inputs.get("Param") or [None])[0]
        gname = (op.inputs.get("Grad") or [None])[0]
        if not pname or not gname:
            continue
        label = pname
        for suf in _PARAM_SUFFIXES:
            if label.endswith(suf):
                label = label[:-len(suf)]
        if label in seen:
            continue
        gvar = gb.vars.get(gname)
        if gvar is not None and (
                gvar.type == VarType.SELECTED_ROWS
                or getattr(gvar, "lod_level", 0)):
            continue
        pvar = gb.vars.get(label)
        if pvar is None or not getattr(pvar, "persistable", False):
            continue
        seen.add(label)
        pairs.append((label, gname))
    plan = HealthPlan(pairs)
    if len(_plan_cache) > 256:
        _plan_cache.clear()
    _plan_cache[key] = plan
    return plan


def plan_if_enabled(program):
    """One flag check when health is off; else the program's plan
    (None when the program has no optimizer ops to watch)."""
    if not flags.get("health"):
        return None
    plan = plan_for(program)
    return plan if plan.pairs else None


def reset():
    _plan_cache.clear()
