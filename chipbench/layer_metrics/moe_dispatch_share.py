"""% of the expert layer's device time OUTSIDE its grouped products: the
router, softmax and top-k, the two sorts, the gathers that permute tokens
into expert order and back, the combine and the router losses."""

from chipbench import scopes


def read(obs):
    red = obs.get("scopes")
    total = scopes.expert_layer_seconds(red, obs) if red else None
    if not total:
        return None
    return 100.0 * (total - scopes.grouped_product_seconds(red, obs)) / total
