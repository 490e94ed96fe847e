"""% of the device's busy time under the `short_conv` op and its backward
(`conv/short_conv/short_conv/...` and `conv/short_conv/short_conv_grad/...`:
the gates, the taps, the filter's gradient; whatever lowers them)."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read
OPS = ("short_conv", "short_conv_grad")


def read(obs):
    return _share(obs, *OPS)
