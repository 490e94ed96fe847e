"""`swa.held_rows_share` for this cell (`keye_vl_2_0_30b_a3b_train_packed8k`: 16 of 128
experts held, 12.5% of the choices if routing is even; the kind times
before it compares)."""

import os

from chipbench import harness

read = harness.load_module(os.path.join(
    os.path.dirname(__file__), "swa.held_rows_share.py")).read
