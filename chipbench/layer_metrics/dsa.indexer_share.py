"""% of the device's busy time in the lightning indexer: its projections,
LayerNorm, rotary and scale (the `indexer` name scope, forward and
backward) and the score product I = sum_j w_j relu(q_I_j . k_I) as the
selection forms it, a block of queries at a time (`indexer_scores`, the
named scope `parallel/sparse_index.py: select` opens inside the
`indexer_select` op). The scores the loss forms again, with their
gradients, are under `dsa.indexer_loss_share`. None where the window holds
neither scope (a program from before the model)."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read
SCOPES = ("indexer", "indexer_scores")


def read(obs):
    return _share(obs, *SCOPES)
