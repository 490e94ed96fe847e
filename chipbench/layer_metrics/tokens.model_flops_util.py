"""Model FLOP/s utilization of a language-model training cell: operations
the forward and backward passes need for a token (from the shapes,
`chipbench/costs_lm.py`: causal attention is half the square, the experts
are the top-k a token meets, nothing recomputed is counted) x tokens/s of
the window, over chips x the table's bf16 peak."""

from chipbench import costs_lm


def read(obs):
    if not obs.get("rate_items_per_s"):
        return None
    per_token = costs_lm.train_flops_per_token(
        obs["cfg"], obs["cfg"]["sequence_length"])
    return (100.0 * per_token * obs["rate_items_per_s"]
            / (obs["chips"] * obs["peaks"]["bf16_flops_per_s"]))
