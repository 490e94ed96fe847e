"""% of the device's busy time in the Mamba-2 mixers (the `mamba` name
scope: the block norm, the input projection, the convolution, the scan,
the gated norm and the output projection, forward and backward, of every
mixer). None where the window holds no such scope (a program from before
the model)."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read


def read(obs):
    return _share(obs, "mamba")
