"""`tokens.expert_cast_share` for this cell: a number wherever the window
holds the expert layer, 0.0 where its weights are read from kept bf16
copies and no cast is left under its scopes."""

import os

from chipbench import harness

read = harness.load_module(os.path.join(
    os.path.dirname(__file__), "tokens.expert_cast_share.py")).read
