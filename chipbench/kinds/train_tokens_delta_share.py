"""Traffic kind `train_tokens_delta_share`: K-step scans of the training
program of a language model that holds one chip's share of the experts and
of the vocabulary, three of whose four layers carry a MATRIX-VALUED STATE
along the sequence (the gated delta rule behind a 4-tap convolution)
beside one output-gated grouped-query attention layer at heads of 256
(`qwen3_next_80b_a3b`), on packed rows of tokens resident on the device,
dispatched one chunk ahead. An item is a token.

The timed loop is `train_tokens_window_share._timed`, imported and not
copied, and with it `token_rows`, `TokenSource`, the `train` kind's
`run_chunks`, `timeline.train_reading` and `scopes.reduce_file`: build,
warm and time FIRST, compare AFTER the window on the window's own chunk 0
(so `setup_s` holds no comparison and `window_peak_bytes` is what the
traffic holds), the timed scan's losses of steps 0 and 1 held to the
reference, and those of step 0 and of the LAST step of its first chunk to
the comparison's second build run step by step through that chunk (what
tells a carried state from one left as it was: at this depth and rate a
step moves the loss by less than bf16 stands from float32, PR 48), and in
every
step fetched: every token routed, the products
took the held experts' rows. That loop names a router bias and replays a
rule over it: this model has neither, its `expert_bias` is zero and its
`router_bias_update_speed` 0, so the replay holds that NOTHING wrote the
bias over every step the executable ran. What this kind has of its own:

* the comparison, `compare_lm_delta_share` (the `gated_delta_rule` op
  alone, output and final state, and the convolution's op alone against
  the reference's token-by-token recurrence and shifted slices on the ops'
  own inputs; the delta branch of the first and of the last delta layer
  and the attention branch first-hand);
* `balance`: whether every layer's held share of the window's choices lies
  within the configuration's `reference.held_share_band` of the even
  share, in the result's `detail` and not in `correct` (the model has no
  rule that evens the load; seeded routers tilt it).
"""

import gc

from chipbench import compare_lm_delta_share
from chipbench.kinds import train_tokens_window_share as window_kind
from chipbench.kinds.train_tokens import TokenSource, token_rows  # noqa: F401


def run(ctx):
    from paddle_tpu import amp

    cfg = ctx.cfg
    if cfg.get("amp"):
        amp.enable(cfg["amp"])
    try:
        res, rows, timed = window_kind._timed(ctx)
        gc.collect()        # the timed program's scope, feeds and futures
        res["reference"] = ref = compare_lm_delta_share.against_reference(
            ctx.fluid, cfg, ctx.builder, ctx.fluid.TPUPlace(0), ctx.seed,
            *rows, timed=timed)
        res["checks"] = dict(reference=bool(ref["ok"]), **res["checks"])
        res["correct"] = all(res["checks"].values())
        even = cfg["num_experts"] / cfg["deployment"]["num_experts"]
        band = cfg["reference"]["held_share_band"]
        res["detail"]["balance"] = all(
            abs(s - even) <= band
            for s in res["detail"]["held_rows_share_by_layer"])
        return res
    finally:
        amp.disable()
