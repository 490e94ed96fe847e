"""Traffic kind `train_tokens_short_conv_share`: K-step scans of the
training program of a language model that holds one chip's share of the
experts and of the vocabulary, most of whose layers are GATED SHORT
CONVOLUTIONS beside a few grouped-query attention layers, and whose head is
the embedding table (`lfm2_8b_a1b`), on packed rows of tokens resident on
the device, dispatched one chunk ahead. An item is a token.

The timed loop is `train_tokens_window_share._timed`, imported and not
copied, and with it `token_rows`, `TokenSource`, the `train` kind's
`run_chunks`, `timeline.train_reading` and `scopes.reduce_file`: build,
warm and time FIRST, compare AFTER the window on the window's own chunk 0
(so `setup_s` holds no comparison and `window_peak_bytes`, the reader
`sconv.peak_hbm_gb`, is what the traffic holds), the timed scan's losses
of steps 0 and 1 held to the reference, the first sparse layer's expert
bias as the scope holds it when the window closes held to the rule
replayed over the router counts of EVERY step the executable ran, and in
every step fetched: every token routed, the products took the held
experts' rows. That loop reads the counts of experts under the Laguna
configuration's key names, which are this configuration's too
(`num_experts`, `num_experts_per_tok`). What this kind has of its own:

* the comparison, `compare_lm_short_conv_share` (the conv branch of the
  dense and of a sparse conv layer and the attention branch first-hand,
  the `short_conv` op alone against the reference's on the op's own input;
  the tied table's gradient: a row only the lookup touches, a row only
  the head weighs, the whole);
* `balance`: whether every sparse layer's held share of the window's
  choices lies within the configuration's `reference.held_share_band` of
  the even share, in the result's `detail` and not in `correct` (a load
  that the seed tilts is a property of random routers, not a wrong
  result).
"""

import gc

from chipbench import compare_lm_short_conv_share
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
        res["reference"] = ref = \
            compare_lm_short_conv_share.against_reference(
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
