"""% of the device's busy time in the indexer's loss (the `indexer_loss`
name scope: the `indexer_loss` op, which forms the head-mean probabilities
of the main attention, the indexer's scores again and the loss's gradient
with respect to q_I, k_I and w in one pass over the query blocks; its grad
op, which scales them; the sum of the layers' losses)."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read


def read(obs):
    return _share(obs, "indexer_loss")
