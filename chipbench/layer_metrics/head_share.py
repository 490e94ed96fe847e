"""% of the device's busy time in the head and its loss (the final norm,
the vocabulary matmul, softmax cross-entropy, forward and backward: the
`lm_head` name scope): what cutting the depth inflates."""

from chipbench import scopes


def read(obs):
    red = obs.get("scopes")
    if not red or not red["busy_s"]:
        return None
    spent = scopes.seconds(red, "lm_head")
    return 100.0 * spent / red["busy_s"] if spent else None
