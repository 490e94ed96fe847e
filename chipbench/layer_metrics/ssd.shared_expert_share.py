"""% of the device's busy time in the shared experts (the `shared` name
scope inside `moe`: two products around relu and a square, forward and
backward, of every expert layer)."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read


def read(obs):
    return _share(obs, "shared")
