"""`swa.held_rows_share` for this cell
(`nemotron_3_nano_30b_a3b_train_packed4k`: 8 of 128 experts held, 6.25% of
the choices if routing is even; the bias rule evens it over the warm-up)."""

import os

from chipbench import harness

read = harness.load_module(os.path.join(
    os.path.dirname(__file__), "swa.held_rows_share.py")).read
