"""% of their roofline the grouped expert products reached: the least
time of the nine products a training step makes (gate, up, down, and each
one's two gradients) over the rows really routed (`costs_lm`; operations
bind at these shapes), over the time of the `ragged-dot-none` custom calls
in the traced window. None unless the trace holds exactly nine a step."""

from chipbench import costs_lm, scopes


def read(obs):
    red = obs.get("scopes")
    spent = scopes.grouped_product_seconds(red, obs) if red else None
    if not spent:
        return None
    least = obs["cfg"]["num_hidden_layers"] * \
        costs_lm.expert_layer_least_seconds(
            obs["cfg"], obs["tokens_per_step"], True, obs["peaks"])
    return 100.0 * least * obs["steps_in_window"] / spent
