"""% of the device's busy time in the attention operator behind its
indexer (the `attn` name scope: the operator norm, the projections, the
per-head QK norms, rotary, the indexer's projections and scores, the
selection, the masked flash kernels, the indexer's loss with its
gradients, the output projection, forward and backward, of every layer).
None where the window holds no such scope."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read


def read(obs):
    return _share(obs, "attn")
