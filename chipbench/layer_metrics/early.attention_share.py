"""`swa.attention_share` for this cell (the same two name scopes)."""

import os

from chipbench import harness

read = harness.load_module(os.path.join(
    os.path.dirname(__file__), "swa.attention_share.py")).read
