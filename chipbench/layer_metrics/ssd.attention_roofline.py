"""% of its roofline the flash kernels of the attention layer reached (32
query heads on 2 key/value heads of D = 128, groups of 16, the whole
triangle), forward, dK/dV and dQ: the least time of their products
(`costs_ssd_share.attention_least_seconds_of`: K and V read once a
key/value head; nothing recomputed counted) over the time of the
operations under the `causal_attention` and `causal_attention_grad` scopes
inside the `attn` name scope. None unless both are in the trace."""

from chipbench import costs_ssd_share as costs
from chipbench import scopes

_FLASH = ("causal_attention", "causal_attention_grad")


def read(obs):
    red, steps = obs.get("scopes"), obs.get("steps_in_window")
    if not red or not steps:
        return None
    fwd, bwd = (sum(s for k, s in red["by_scope"].items()
                    if scopes.in_scope(k, "attn") and scopes.in_scope(k, op))
                for op in _FLASH)
    if not fwd or not bwd:
        return None
    least = costs.attention_least_seconds_of(obs["cfg"], True, obs["peaks"])
    return 100.0 * least * steps / (fwd + bwd)
