"""`tokens.expert_cast_share` for this cell
(`nemotron_3_nano_30b_a3b_train_packed4k`: 8 of 128 experts held, two
stacked matrices a layer; 0.0 where the step reads the bf16 copies its
updates keep)."""

import os

from chipbench import harness

read = harness.load_module(os.path.join(
    os.path.dirname(__file__), "tokens.expert_cast_share.py")).read
