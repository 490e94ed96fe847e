"""% of the expert layers' device time OUTSIDE their grouped kernels (the
router over all 128 experts and its top-8, the sorts, the row gathers, the
zeroing of rows past the groups and the combine): `gdn.expert_other_share` for this cell. The share
path and the configuration's keys it reads (`hidden_size`,
`moe_intermediate_size`, `num_experts` held, `num_hidden_layers`: nine
kernels a layer and step) are the Qwen3-Next cell's, so the reader is
that one, not a copy."""

import os

from chipbench import harness

_reader = harness.load_module(os.path.join(
    os.path.dirname(__file__), "gdn.expert_other_share.py"))
read = _reader.read
