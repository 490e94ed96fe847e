"""% of its roofline the flash kernels of the full-attention layer
reached (no rotary before it, groups of 7 query heads on one key/value
head), forward, dK/dV and dQ: the least time of their products over the
triangle over the time of the operations under the `causal_attention` and
`causal_attention_grad` scopes inside the `attn_full` name scope
(`early.window_attention_roofline.read`, told the other kind). None
unless both are in the trace."""

import os

from chipbench import costs_early_route_share as costs
from chipbench import harness

_window = harness.load_module(os.path.join(
    os.path.dirname(__file__), "early.window_attention_roofline.py"))


def read(obs):
    return _window.read(obs, costs.FULL, "attn_full")
