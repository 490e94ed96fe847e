"""% of the device's busy time in the attention sublayers, both kinds (the
`attn_full` and `attn_window` name scopes: projections, rotary, the flash
kernels, the per-head gate, the output projection, forward and
backward)."""

from chipbench import scopes


def read(obs):
    red = obs.get("scopes")
    if not red or not red["busy_s"]:
        return None
    spent = scopes.seconds(red, "attn_full", "attn_window")
    return 100.0 * spent / red["busy_s"] if spent else None
