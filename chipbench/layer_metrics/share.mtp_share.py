"""% of the device's busy time in the multi-token-prediction module (the
`mtp` name scope: its projection, its own decoder block, its norm, the
shared head's second use and its cross-entropy, forward and backward)."""

from chipbench import scopes


def read(obs):
    red = obs.get("scopes")
    if not red or not red["busy_s"]:
        return None
    spent = scopes.seconds(red, "mtp")
    return 100.0 * spent / red["busy_s"] if spent else None
