"""Device seconds of a traced window by the Fluid op the operations came
from: the executor emits an op's lowering under `jax.named_scope` (the op
type, under the `fluid.name_scope`s it was appended in), XLA keeps that as
the instruction's `op_name`, and the TPU profiler writes it as the stat
`tf_op` of the event's metadata, which `xplane.load` already parses.

Looked at by hand first (the recorded trace `chipbench/data/small.xplane.pb`,
PR 23, and the first trace of the `olmoe_1b_7b` cell, PR 26; jax 0.9.0,
TPU v5 lite): `tf_op` reads `jit(step)/convnet/conv_general_dilated:`,
a fusion carries the op_name of its root instruction, and what XLA makes
itself carries its own name and no scope: the grouped products of
`lax.ragged_dot` are Mosaic custom calls named `ragged-dot-none`, copies
and sorts are bare. Those are filed under `[xla]<name>`.
"""

import re

from chipbench import xplane

# path components of an op_name that JAX or XLA wrote, not the program
_WRAPPER = re.compile(r"^(jit|pjit|jvp|transpose|vmap|remat|checkpoint|"
                      r"custom_jvp|custom_vjp|custom_vjp_call|shard_map)\(")
_CONTROL = {"while", "body", "cond", "closed_call", "core_call"}
GROUPED_PRODUCT = "ragged-dot-none"
# what the expert layer's device time is made of: the op's two scopes and
# the custom calls XLA makes of its `lax.ragged_dot`s
MOE_OPS = ("moe_ffn", "moe_ffn_grad")
MOE_XLA = (GROUPED_PRODUCT, "ragged-dot-metadata")


def _split(path):
    """Components of an op_name: split at the `/` outside parentheses
    (`transpose(jvp(attn/causal_attention_grad))` is one component)."""
    parts, depth, cur = [], 0, ""
    for ch in path:
        if ch == "/" and depth == 0:
            parts.append(cur)
            cur = ""
            continue
        depth += (ch == "(") - (ch == ")")
        cur += ch
    return parts + [cur]


def scope_of(tf_op):
    """`jit(multi)/while/body/moe/moe_ffn/jit(argsort)/sort:` ->
    `moe/moe_ffn`: the program's part of an op_name. The last component is
    the JAX primitive; wrappers and control flow are JAX's."""
    parts = [p for p in _split(str(tf_op or "").rstrip(":")) if p]
    kept = [p for p in parts[:-1]
            if not _WRAPPER.match(p) and p not in _CONTROL
            and not re.match(r"^branch_\d+_fun$", p)]
    return "/".join(kept)


def event_scope(ev):
    scope = scope_of(ev.stats.get("tf_op"))
    if scope:
        return scope
    return "[xla]" + xplane.op_base(ev.name)


def in_scope(key, *names):
    """Whether a scope key has one of `names` among its path components
    (`moe_ffn` matches `moe/moe_ffn` and not `moe/rms_norm`)."""
    return any(part in names for part in key.split("/"))


def reduce_planes(planes, host=None):
    """Seconds by scope over the window (`host` as `xplane.reduce_trace`
    takes it: the harness's window and clock markers), chip 0. Returns
    None without a device plane or without the markers."""
    devs = xplane.device_planes(planes)
    ln = devs[0].line(xplane.OPS_LINE) if devs else None
    if ln is None or not ln.events:
        return None
    lo, hi = ln.events[0].start_ps, max(e.end_ps for e in ln.events)
    if host is not None:
        clock = xplane.marker_offset_ps(planes, host["syncs"])
        if clock is None:
            return None
        lo, hi = (int(v * 1e12) - clock for v in host["window"])
    events = [e for e in ln.events if e.end_ps > lo and e.start_ps < hi
              and xplane.MARKER not in e.name]
    busy = xplane.total(xplane.clip(
        xplane.union((e.start_ps, e.end_ps) for e in events), lo, hi))
    by_scope, by_op, counts = {}, {}, {}
    for e, self_ps in zip(events, xplane.self_times(events)):
        key = event_scope(e)
        by_scope[key] = by_scope.get(key, 0) + self_ps
        counts[key] = counts.get(key, 0) + 1
        if not scope_of(e.stats.get("tf_op")):
            name = xplane.stable_name(e)
            by_op[name] = by_op.get(name, 0) + self_ps
    ps = 1e-12
    return {"window_s": (hi - lo) * ps, "busy_s": busy * ps,
            "by_scope": {k: v * ps for k, v in by_scope.items()},
            "events": counts,
            "unscoped_ops": {k: v * ps for k, v in by_op.items()}}


def reduce_file(path, host=None):
    return reduce_planes(xplane.load(path), host=host)


def seconds(red, *names, xla=()):
    """Seconds under the scopes that have one of `names` among their
    components, plus the XLA-made operations named in `xla`."""
    return sum(s for k, s in red["by_scope"].items()
               if in_scope(k, *names) or k in {"[xla]" + n for n in xla})


def unscoped_share(red):
    """% of busy time in operations that no scope of the program names
    and that are not the expert layer's by their own name."""
    if not red["busy_s"]:
        return None
    named = {"custom-call:" + n for n in MOE_XLA}
    return 100.0 * sum(s for k, s in red["unscoped_ops"].items()
                       if k not in named) / red["busy_s"]


def grouped_product_seconds(red, obs):
    """Seconds of the expert layer's grouped products in the window; None
    unless the trace holds exactly the number a step makes (gate, up, down
    and each one's two gradients, a layer) times the window's steps: the
    name is XLA's and carries no scope of the program, so a runtime that
    renames or re-fuses them must read as nothing, not as a smaller
    number."""
    from chipbench import costs_lm

    key = "[xla]" + GROUPED_PRODUCT
    steps = obs.get("steps_in_window")
    if not steps or red["events"].get(key) != steps * costs_lm.expert_products(
            True) * obs["cfg"]["num_hidden_layers"]:
        return None
    return red["by_scope"][key] or None


def expert_layer_seconds(red, obs):
    """Seconds of the expert layer, forward and backward; None unless the
    op's own scopes and every grouped product are in the trace."""
    if not seconds(red, *MOE_OPS) or not grouped_product_seconds(red, obs):
        return None
    return seconds(red, *MOE_OPS, xla=MOE_XLA)
