"""% of their roofline the grouped products reached, with the SiLU
epilogues in them, at K 2048 / F 768 and ~512 rows a group over 16 held
groups: `gdn.grouped_matmul_roofline` for this cell. The share
path and the configuration's keys it reads (`hidden_size`,
`moe_intermediate_size`, `num_experts` held, `num_hidden_layers`: nine
kernels a layer and step) are the Qwen3-Next cell's, so the reader is
that one, not a copy."""

import os

from chipbench import harness

_reader = harness.load_module(os.path.join(
    os.path.dirname(__file__), "gdn.grouped_matmul_roofline.py"))
read = _reader.read
note = _reader.note
