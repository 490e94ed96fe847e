"""`moe_share` in the token cells, whichever way the program lowers its
grouped products: `moe_share.py` where the trace holds XLA's
`ragged-dot-none` calls; else, where it holds the program's own Pallas
kernels, nine a step and layer, the `moe_ffn` / `moe_ffn_grad` scopes
alone (the kernels' keys lie under them); see
`tokens.expert_matmul_roofline.py`."""

from chipbench import scopes
from chipbench.layer_metrics import grouped_matmul_roofline, moe_share


def read(obs):
    value = moe_share.read(obs)
    red = obs.get("scopes")
    if value is not None or not red or not red["busy_s"] or \
            not grouped_matmul_roofline.kernel_seconds(red, obs):
        return value
    return 100.0 * scopes.seconds(red, *scopes.MOE_OPS) / red["busy_s"]
