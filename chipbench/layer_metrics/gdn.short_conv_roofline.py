"""% of its roofline the `short_conv` op (gating "silu": silu(conv4(x))
over the 8192 q, k and v channels) reached, forward and backward, over the
delta layers of the window's steps: the least seconds of its bytes
(`costs_delta_share.short_conv_least_seconds_of`: X in and Out out; X and d
Out in and d X out) over the seconds of the operations under the op's two
scopes. None unless both scopes are in the trace."""

from chipbench import costs_delta_share as costs
from chipbench import scopes


def read(obs):
    red, steps = obs.get("scopes"), obs.get("steps_in_window")
    if not red or not steps:
        return None
    fwd = sum(s for k, s in red["by_scope"].items()
              if scopes.in_scope(k, "short_conv")
              and not scopes.in_scope(k, "short_conv_grad"))
    bwd = scopes.seconds(red, "short_conv_grad")
    if not fwd or not bwd:
        return None
    least = costs.short_conv_least_seconds_of(obs["cfg"], True, obs["peaks"])
    return 100.0 * least * steps / (fwd + bwd)
