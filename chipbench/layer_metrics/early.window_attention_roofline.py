"""% of its roofline the flash kernels of the sliding-window layers
reached, forward, dK/dV and dQ, over the three window layers: the least
time of their products over the BAND (`costs_early_route_share`: S W -
W^2 / 2 pairs a head at W 4096, K and V read once for the group of 7;
nothing recomputed counted) over the time of the operations under the
`causal_attention` and `causal_attention_grad` scopes inside the
`attn_window` name scope. None unless both are in the trace."""

from chipbench import costs_early_route_share as costs
from chipbench import scopes

_FLASH = ("causal_attention", "causal_attention_grad")


def read(obs, kind=costs.WINDOW, scope="attn_window"):
    red, steps = obs.get("scopes"), obs.get("steps_in_window")
    if not red or not steps:
        return None
    fwd, bwd = (sum(s for k, s in red["by_scope"].items()
                    if scopes.in_scope(k, scope) and scopes.in_scope(k, op))
                for op in _FLASH)
    if not fwd or not bwd:
        return None
    least = costs.attention_least_seconds_of(obs["cfg"], kind, True,
                                             obs["peaks"])
    return 100.0 * least * steps / (fwd + bwd)
