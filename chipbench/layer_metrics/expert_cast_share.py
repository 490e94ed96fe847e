"""% of the device's busy time in the casts the mixed-precision policy
makes for the expert layer, forward and backward: the float32 -> bf16
casts of the gate, up and down weights (the masters are float32; a
Pallas kernel fuses no producer, so each cast is a pass of its own).
Sub-scope `cast` (`paddle_tpu/amp.py: CAST_SCOPE`) inside the `moe_ffn` /
`moe_ffn_grad` scopes. None where the window holds no such key."""

from chipbench.layer_metrics.expert_move_share import part_share


def read(obs):
    return part_share(obs, "cast")
