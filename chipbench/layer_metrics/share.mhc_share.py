"""% of the device's busy time in the residual path of several streams
(the `mhc` name scope: `mhc_expand`, the mixers `mhc_mix` with their
projections and Sinkhorn rounds, `mhc_update`, forward and backward):
bandwidth-bound passes over the [tokens, streams, hidden] state."""

from chipbench import scopes


def read(obs):
    red = obs.get("scopes")
    if not red or not red["busy_s"]:
        return None
    spent = scopes.seconds(red, "mhc")
    return 100.0 * spent / red["busy_s"] if spent else None
