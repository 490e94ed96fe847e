"""`swa.window_blocks_visited_share` for this cell: a band of 4096 in rows
of 8192 is three quarters of the triangle's pairs, so the share of blocks
built to be visited is high by the model's own shape."""

import os

from chipbench import harness

read = harness.load_module(os.path.join(
    os.path.dirname(__file__), "swa.window_blocks_visited_share.py")).read
