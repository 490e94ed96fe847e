"""% of the device's busy time under the `gated_delta_rule` op and its
backward (`delta/delta_rule/gated_delta_rule/...` and `.../
gated_delta_rule_grad/...`: the in-chunk products, the triangular inverse,
the scan over the chunks and its reverse; whatever lowers them)."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read
OPS = ("gated_delta_rule", "gated_delta_rule_grad")


def read(obs):
    return _share(obs, *OPS)
