"""% of the device's busy time in the attention operator (the `attn` name
scope: the operator norm, the projections, the per-head QK norms, rotary,
the flash kernels, the output projection, forward and backward)."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read


def read(obs):
    return _share(obs, "attn")
