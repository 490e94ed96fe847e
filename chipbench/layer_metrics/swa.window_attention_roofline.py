"""% of its roofline the flash kernels of the sliding-window layers
reached, forward, dK/dV and dQ, over every window layer: the least time of
their products over the BAND (`costs_window_share`: S W - W^2 / 2 pairs a
head, K and V read once a key/value head; nothing recomputed counted,
neither the backward's scores nor the masked pairs of a visited block)
over the time of the operations under the `causal_attention` and
`causal_attention_grad` scopes inside the `attn_window` name scope. None
unless both are in the trace."""

from chipbench import costs_window_share, scopes

KIND, SCOPE = costs_window_share.WINDOW, "attn_window"
_FLASH = ("causal_attention", "causal_attention_grad")


def kernel_seconds(red, scope, op):
    return sum(s for k, s in red["by_scope"].items()
               if scopes.in_scope(k, scope) and scopes.in_scope(k, op))


def read(obs, kind=KIND, scope=SCOPE):
    red, steps = obs.get("scopes"), obs.get("steps_in_window")
    if not red or not steps:
        return None
    fwd, bwd = (kernel_seconds(red, scope, op) for op in _FLASH)
    if not fwd or not bwd:
        return None
    least = costs_window_share.attention_least_seconds_of(
        obs["cfg"], kind, True, obs["peaks"])
    return 100.0 * least * steps / (fwd + bwd)
