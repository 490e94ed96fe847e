"""% of the device's busy time in the expert layer, forward and backward:
operations lowered under the `moe_ffn` / `moe_ffn_grad` scopes, plus the
grouped products XLA makes of `lax.ragged_dot` (Mosaic custom calls named
`ragged-dot-none`, which carry no scope; `chipbench/scopes.py`). None
unless the trace holds every one of those products."""

from chipbench import scopes


def read(obs):
    red = obs.get("scopes")
    spent = scopes.expert_layer_seconds(red, obs) if red else None
    if not spent or not red["busy_s"]:
        return None
    return 100.0 * spent / red["busy_s"]
