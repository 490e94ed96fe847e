"""`expert_cast_share` (% of the device's busy time in the float32 -> bf16
casts of the expert layer's gate, up and down weights, sub-scope `cast`
inside the `moe_ffn` / `moe_ffn_grad` scopes) as a number wherever the
window holds an expert layer that names its parts: 0.0 where the other
parts (`route`, `dispatch`, `combine`) are there under `moe_ffn*` and no
`cast` key is, which is what a step reads whose updates keep the bf16
copies (`paddle_tpu/amp.py: KERNEL_SLOTS`): there is no cast left to time.
The base reader gives None there, and a traced line that lacks an
accepted metric is refused. None where the window holds no `moe_ffn`
scope, or one without sub-scopes (a program from before they were
opened cannot say where its casts are)."""

from chipbench.layer_metrics.expert_move_share import part_share


def read(obs):
    share = part_share(obs, "cast")
    if share is None and part_share(obs, "route", "dispatch",
                                    "combine") is not None:
        return 0.0
    return share
