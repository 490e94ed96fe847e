"""% of its roofline the flash kernels of the full-attention layers
reached, forward, dK/dV and dQ, over every full layer: the least time of
their products over the triangle (`costs_window_share`: S^2 / 2 pairs a
head, K and V read once a key/value head; nothing recomputed counted) over
the time of the operations under the `causal_attention` and
`causal_attention_grad` scopes inside the `attn_full` name scope
(`swa.window_attention_roofline.read`, told the other kind). None unless
both are in the trace."""

import os

from chipbench import costs_window_share, harness

_window = harness.load_module(os.path.join(
    os.path.dirname(__file__), "swa.window_attention_roofline.py"))


def read(obs):
    return _window.read(obs, costs_window_share.FULL, "attn_full")
