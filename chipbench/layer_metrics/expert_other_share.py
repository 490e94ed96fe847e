"""% of the expert layer's device time OUTSIDE its grouped kernels: of the
seconds under the `moe_ffn` and `moe_ffn_grad` scopes (the kernels' own
keys lie under them), the part that is the router, softmax and top-k, the
two sorts, the row gathers into expert order and back, the visit lists,
SiLU, the combine and the router losses. None where
the trace holds none of the grouped kernels, whatever their count
(`grouped_matmul_roofline.kernel_seconds`)."""

from chipbench import scopes
from chipbench.layer_metrics import grouped_matmul_roofline


def read(obs):
    red = obs.get("scopes")
    kernels = grouped_matmul_roofline.kernel_seconds(red, obs) if red \
        else None
    if not kernels:
        return None
    total = scopes.seconds(red, *scopes.MOE_OPS)
    return 100.0 * (total - kernels) / total
