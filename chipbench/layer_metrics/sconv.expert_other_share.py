"""% of the expert layers' device time OUTSIDE their grouped kernels: of
the seconds under the `moe_ffn` and `moe_ffn_grad` scopes, the router over
all 32 experts, the sorts, the row gathers into expert order and back, the
zeroing of rows past the groups and the combine. None where
the trace holds none of the grouped kernels, whatever their count
(`sconv.grouped_matmul_roofline.kernel_seconds`)."""

import os

from chipbench import harness, scopes

_kernels = harness.load_module(os.path.join(
    os.path.dirname(__file__), "sconv.grouped_matmul_roofline.py"))


def read(obs):
    red = obs.get("scopes")
    kernels = _kernels.kernel_seconds(red, obs) if red else None
    if not kernels:
        return None
    total = scopes.seconds(red, *scopes.MOE_OPS)
    return 100.0 * (total - kernels) / total
