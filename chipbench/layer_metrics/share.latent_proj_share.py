"""% of the device's busy time in latent attention OUTSIDE the flash
kernels (the `attn` name scope but `causal_attention` and its grad): the
down- and up-projections of queries and keys/values, their norms, the
rotary part, the splits and concatenations, the output projection."""

from chipbench import scopes

_FLASH = ("causal_attention", "causal_attention_grad")


def read(obs):
    red = obs.get("scopes")
    if not red or not red["busy_s"]:
        return None
    spent = sum(s for k, s in red["by_scope"].items()
                if scopes.in_scope(k, "attn")
                and not scopes.in_scope(k, *_FLASH))
    return 100.0 * spent / red["busy_s"] if spent else None
