"""% of their roofline the expert layer's grouped products reached, where
they run as the program's own Pallas kernels: the least time of the nine
products a training step makes (`costs_lm.expert_layer_least_seconds`:
gate, up, down and each one's two gradients over the rows really routed;
operations bind) over the seconds of the kernels in the traced window.

The kernels are found by their names (`grouped_matmul` forward,
`grouped_matmul_nt` d lhs, `grouped_matmul_tn` d rhs): Pallas puts a
kernel's name on the op_name's path, so the scope key reads
`moe/moe_ffn/grouped/grouped_matmul` forward and
`moe/moe_ffn_grad/grouped_matmul_nt` backward. None unless the trace holds exactly nine a step and layer: a
program whose products run under another name (XLA's `ragged-dot-none`
before PR 29) has nothing to read here."""

from chipbench import costs_lm, scopes

KERNELS = ("grouped_matmul", "grouped_matmul_nt", "grouped_matmul_tn")


def kernel_seconds(red, obs):
    """Seconds of the grouped kernels in the window; None unless their
    events are nine a step and layer."""
    keys = [k for k in red["by_scope"] if scopes.in_scope(k, *KERNELS)]
    steps = obs.get("steps_in_window")
    want = (steps or 0) * costs_lm.expert_products(True) \
        * obs["cfg"]["num_hidden_layers"]
    if not want or sum(red["events"].get(k, 0) for k in keys) != want:
        return None
    return sum(red["by_scope"][k] for k in keys) or None


def read(obs):
    red = obs.get("scopes")
    spent = kernel_seconds(red, obs) if red else None
    if not spent:
        return None
    least = obs["cfg"]["num_hidden_layers"] * \
        costs_lm.expert_layer_least_seconds(
            obs["cfg"], obs["tokens_per_step"], True, obs["peaks"])
    return 100.0 * least * obs["steps_in_window"] / spent
