"""`swa.peak_hbm_gb` for this cell: the kind times before it compares, so
the high-water mark read as the window closes is what the traffic holds."""

import os

from chipbench import harness

read = harness.load_module(os.path.join(
    os.path.dirname(__file__), "swa.peak_hbm_gb.py")).read
