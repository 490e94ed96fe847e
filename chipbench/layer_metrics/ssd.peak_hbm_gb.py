"""`swa.peak_hbm_gb` for this cell
(`nemotron_3_nano_30b_a3b_train_packed4k`: the kind times before it
compares, so this is what the traffic holds)."""

import os

from chipbench import harness

read = harness.load_module(os.path.join(
    os.path.dirname(__file__), "swa.peak_hbm_gb.py")).read
