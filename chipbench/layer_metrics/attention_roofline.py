"""% of its roofline causal attention reached, forward and backward: the
least time of its products over half the square (`costs_lm`: 2 forward, 4
backward, the recomputed scores not counted) over the time of the
operations under the `causal_attention` and `causal_attention_grad`
scopes (the flash kernel, and its backward as lowered). None unless both
scopes are in the trace."""

from chipbench import costs_lm, scopes


def read(obs):
    red, steps = obs.get("scopes"), obs.get("steps_in_window")
    if not red or not steps:
        return None
    fwd = scopes.seconds(red, "causal_attention")
    bwd = scopes.seconds(red, "causal_attention_grad")
    if not fwd or not bwd:
        return None
    cfg = obs["cfg"]
    least = cfg["num_hidden_layers"] * costs_lm.attention_least_seconds(
        cfg, cfg["rows_per_step"], cfg["sequence_length"], True,
        obs["peaks"])
    return 100.0 * least * steps / (fwd + bwd)
