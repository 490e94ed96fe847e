"""% of its roofline the indexer's score product reached, forward and
backward, over the layers of the window's steps: the least seconds of the
WORK (`costs_sparse_attn_share.indexer_least_seconds_of`: EVERY CAUSAL
pair, 2 x 16 x 64 operations forward and its two gradients, against q_I,
k_I, w read, the mask written and the gradients written, at `peaks.json`)
over the seconds of the operations that form and differentiate the scores:
`indexer_scores` inside the `indexer_select` op (the selection's block-wise
product) and the whole `indexer_loss` / `indexer_loss_grad` ops (which form
the scores again a block of queries at a time, their two gradients, and
the head-mean probabilities the loss is taken against). What the lowering
does beyond the least, the second score product, the probabilities' pass
over the main attention's scores, float32 temporaries in HBM, shows as a
share below 100. None unless both are in the trace."""

from chipbench import costs_sparse_attn_share as costs
from chipbench import scopes


def read(obs):
    red, steps = obs.get("scopes"), obs.get("steps_in_window")
    if not red or not steps:
        return None
    formed = scopes.seconds(red, "indexer_scores")
    trained = scopes.seconds(red, "indexer_loss", "indexer_loss_grad")
    if not formed or not trained:
        return None
    least = costs.indexer_least_seconds_of(obs["cfg"], True, obs["peaks"])
    return 100.0 * least * steps / (formed + trained)
