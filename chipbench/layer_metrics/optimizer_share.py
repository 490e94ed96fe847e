"""% of the device's busy time in the optimizer: the update ops (`adam`,
or the bucketed `fused_adam_update` under FLAGS_fuse; `optimizer` scope)
and the global-norm clip (`gradient_clip` scope)."""

from chipbench import scopes


def read(obs):
    red = obs.get("scopes")
    if not red or not red["busy_s"]:
        return None
    spent = scopes.seconds(red, "optimizer", "gradient_clip")
    return 100.0 * spent / red["busy_s"] if spent else None
