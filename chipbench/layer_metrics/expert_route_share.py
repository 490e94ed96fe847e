"""% of the device's busy time in the expert layer's router, forward and
backward: the router's products at full precision, softmax or sigmoid +
bias, `top_k`, the chosen scores, the two argsorts, the counts and the
two router losses. Sub-scope `route` (`paddle_tpu/ops/lm_ops.py: ROUTE`)
inside the `moe_ffn` / `moe_ffn_grad` scopes. None where the window holds
no such key."""

from chipbench.layer_metrics.expert_move_share import part_share


def read(obs):
    return part_share(obs, "route")
