"""% of the device's busy time in the conv operators (the `conv` name
scope: the operator norm, the input projection, `short_conv`, the output
projection, forward and backward, of every conv layer). None where the
window holds no such scope (a program from before the model)."""

from chipbench import scopes


def read(obs, *names):
    red = obs.get("scopes")
    if not red or not red["busy_s"]:
        return None
    spent = scopes.seconds(red, *(names or ("conv",)))
    return 100.0 * spent / red["busy_s"] if spent else None
