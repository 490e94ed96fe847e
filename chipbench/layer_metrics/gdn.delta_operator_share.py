"""% of the device's busy time in the Gated DeltaNet operators (the `delta`
name scope: the operator norm, the two input projections, the 4-tap
convolution, the delta rule, the gated norm and the output projection,
forward and backward, of every delta layer). None where the window holds
no such scope (a program from before the model)."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read


def read(obs):
    return _share(obs, "delta")
