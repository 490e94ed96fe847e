"""% of its roofline latent attention's flash kernels reached, forward and
backward, over every block: the least time of the products over half the
square at the two head sizes (`costs_share`: queries and keys 192, values
128; nothing recomputed counted, neither the backward's scores nor the
forward of a block evaluated twice) over the time of the operations under
the `causal_attention` and `causal_attention_grad` scopes. None unless
both scopes are in the trace."""

from chipbench import costs_share, scopes


def read(obs):
    red, steps = obs.get("scopes"), obs.get("steps_in_window")
    if not red or not steps:
        return None
    fwd = scopes.seconds(red, "causal_attention")
    bwd = scopes.seconds(red, "causal_attention_grad")
    if not fwd or not bwd:
        return None
    cfg = obs["cfg"]
    least = sum(costs_share.blocks(cfg)) * \
        costs_share.attention_least_seconds(cfg, True, obs["peaks"])
    return 100.0 * least * steps / (fwd + bwd)
