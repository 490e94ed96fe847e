"""% of the device's busy time under the mixers' `short_conv` op (gating
"silu", with its bias) and its backward (`mamba/conv/short_conv/...`,
`.../short_conv_grad/...`)."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read
OPS = ("short_conv", "short_conv_grad")


def read(obs):
    return _share(obs, *OPS)
