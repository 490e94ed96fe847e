"""`moe_dispatch_share` in the token cells, whichever way the program
lowers its grouped products: the expert layer's device time outside XLA's
`ragged-dot-none` calls where the trace holds them
(`moe_dispatch_share.py`), else outside the program's own Pallas kernels
(`expert_other_share.py`); see `tokens.expert_matmul_roofline.py`."""

from chipbench.layer_metrics import expert_other_share, moe_dispatch_share


def read(obs):
    value = moe_dispatch_share.read(obs)
    return expert_other_share.read(obs) if value is None else value
