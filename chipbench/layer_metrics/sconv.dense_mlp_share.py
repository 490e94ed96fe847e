"""% of the device's busy time in the dense SwiGLU of the leading layer
(the `dense_mlp` name scope: its norm, three products and the gate,
forward and backward)."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read


def read(obs):
    return _share(obs, "dense_mlp")
