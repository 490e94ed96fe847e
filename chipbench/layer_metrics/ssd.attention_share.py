"""% of the device's busy time in the attention layer (the `attn` name
scope: the block norm, the four projections and the flash kernels, forward
and backward; no rotary: the layer has no positions)."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read


def read(obs):
    return _share(obs, "attn")
