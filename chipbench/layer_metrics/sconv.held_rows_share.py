"""`swa.held_rows_share` for this cell: % of the top_k x tokens choices of
the first sparse layer that fell on the 8 experts this chip holds of 32 (25
if routing is even), median of the window's steps."""

import os

from chipbench import harness

read = harness.load_module(os.path.join(
    os.path.dirname(__file__), "swa.held_rows_share.py")).read
