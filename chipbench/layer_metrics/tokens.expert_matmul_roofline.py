"""`expert_matmul_roofline` in the token cells, whichever way the program
lowers its grouped products: XLA's `ragged-dot-none` calls where the trace
holds them (`expert_matmul_roofline.py`, the program before PR 29), else
the program's own Pallas kernels (`grouped_matmul_roofline.py`). The same
quantity either way: the least time of the nine products a step over the
seconds they took. A line without it is refused, so the metric follows the
products through the change of lowering; which of the two names stays is
the next `benchmark` issue's (PERF.md section 7)."""

from chipbench.layer_metrics import (expert_matmul_roofline,
                                     grouped_matmul_roofline)


def read(obs):
    value = expert_matmul_roofline.read(obs)
    return grouped_matmul_roofline.read(obs) if value is None else value
