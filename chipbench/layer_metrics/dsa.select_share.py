"""% of the device's busy time in the selection proper: under the
`indexer_select` op, what is NOT the score product (`indexer_scores`,
counted under `dsa.indexer_share`): the fold of the scores' bits into
ordered integers, the 32 counting passes of the bisection a block of
queries, the running count that gives ties to the lower position, the
mask's write. None where the window holds no `indexer_select` scope."""

from chipbench import scopes


def read(obs):
    red = obs.get("scopes")
    if not red or not red["busy_s"]:
        return None
    spent = sum(s for k, s in red["by_scope"].items()
                if scopes.in_scope(k, "indexer_select")
                and not scopes.in_scope(k, "indexer_scores"))
    return 100.0 * spent / red["busy_s"] if spent else None
