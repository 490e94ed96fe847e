"""% of the device's busy time the expert layer spends MOVING rows around
its products, forward and backward: the `dispatch` part of the op's
lowering (row gathers into expert order and their backward, the zeroing
of `ys` / `d xs` past the groups) and the `combine` part (the gather a
choice, the weights, the sums over the choices, `DownOut`'s zero tail, the
weights' gradient). The program names the parts itself: sub-scopes inside
the `moe_ffn` / `moe_ffn_grad` scopes (`paddle_tpu/ops/lm_ops.py: DISPATCH,
COMBINE`), so the key of an operation reads `moe/moe_ffn_grad/combine`.
None where the window holds no such key (a program without the
sub-scopes)."""

from chipbench import scopes


def part_share(obs, *parts):
    """% of busy time under the expert layer's scopes AND one of the
    sub-scopes `parts`; None where nothing is."""
    red = obs.get("scopes")
    if not red or not red["busy_s"]:
        return None
    spent = sum(s for k, s in red["by_scope"].items()
                if scopes.in_scope(k, *scopes.MOE_OPS)
                and scopes.in_scope(k, *parts))
    return 100.0 * spent / red["busy_s"] if spent else None


def read(obs):
    return part_share(obs, "dispatch", "combine")
