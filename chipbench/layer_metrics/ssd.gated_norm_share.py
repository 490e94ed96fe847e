"""% of the device's busy time in the mixers' gated norm (the `gated_norm`
name scope: y * silu(z) and the grouped RMSNorm over each group's 512
numbers, forward and backward): two element-wise Fluid ops and a norm that
XLA fuses or does not; the number says whether a fused op is worth it."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read


def read(obs):
    return _share(obs, "gated_norm")
