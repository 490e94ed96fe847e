"""`tokens.expert_cast_share` for this cell: 0.0 where the expert weights
are read from kept bf16 copies and no cast is left under the op's scopes."""

import os

from chipbench import harness

read = harness.load_module(os.path.join(
    os.path.dirname(__file__), "tokens.expert_cast_share.py")).read
