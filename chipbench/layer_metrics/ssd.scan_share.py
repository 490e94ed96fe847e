"""% of the device's busy time under the `ssd_scan` op and its backward
(`mamba/scan/ssd_scan/...` and `.../ssd_scan_grad/...`: the in-chunk
products, the decays, the scan over the chunks and its reverse; whatever
lowers them)."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read
OPS = ("ssd_scan", "ssd_scan_grad")


def read(obs):
    return _share(obs, *OPS)
