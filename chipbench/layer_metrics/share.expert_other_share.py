"""% of the expert layer's device time OUTSIDE its grouped kernels, in a
model whose layers hold a share of their experts: of the seconds under
the `moe_ffn` and `moe_ffn_grad` scopes, the router over all experts, the
sorts, the row gathers into expert order and back over ALL top_k x tokens
rows (the shape is static, the held rows a part of it), the visit lists,
the zeroing of rows past the groups, SiLU and the combine. None where
the trace holds none of the grouped kernels, whatever their count
(`share.grouped_matmul_roofline.kernel_seconds`)."""

import os

from chipbench import harness, scopes

_kernels = harness.load_module(os.path.join(
    os.path.dirname(__file__), "share.grouped_matmul_roofline.py"))


def read(obs):
    red = obs.get("scopes")
    kernels = _kernels.kernel_seconds(red, obs) if red else None
    if not kernels:
        return None
    total = scopes.seconds(red, *scopes.MOE_OPS)
    return 100.0 * (total - kernels) / total
