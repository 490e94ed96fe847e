"""% of the device's busy time under the `sparse_attention` op and its
backward: the three masked flash kernels (`sparse_flash_fwd`,
`sparse_flash_dkv`, `sparse_flash_dq`), the transposed copy of the mask
the dK/dV kernel reads and the layout changes around them."""

import os

from chipbench import harness

_share = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.conv_operator_share.py")).read
OPS = ("sparse_attention", "sparse_attention_grad")


def read(obs):
    return _share(obs, *OPS)
