"""Traffic kind `train_tokens_ssd_share`: K-step scans of the training
program of a language model that holds one chip's share of the experts and
of the vocabulary and whose layers are ONE branch each: Mamba-2 mixers (a
selective state-space scan behind a 4-tap convolution with a bias), un-gated
relu^2 experts and one attention layer without positions
(`nemotron_3_nano_30b_a3b`), on packed rows of tokens resident on the
device, dispatched one chunk ahead. An item is a token.

The timed loop is `train_tokens_window_share._timed`, imported and not
copied, and with it `token_rows`, `TokenSource`, the `train` kind's
`run_chunks`, `timeline.train_reading` and `scopes.reduce_file`: build,
warm and time FIRST, compare AFTER the window on the window's own chunk 0
(so `setup_s` holds no comparison and `window_peak_bytes`, the reader
`ssd.peak_hbm_gb`, is what the traffic holds), the timed scan's losses of
steps 0 and 1 held to the reference, the first expert layer's bias of the
choice as the scope holds it when the window closes held to the rule
replayed over the router counts of EVERY step the executable ran, and in
every step fetched: every token routed, the products took the held
experts' rows. That loop reads the counts of experts under the Laguna
configuration's key names: this kind hands it the configuration with
`n_routed_experts` under `num_experts` as well (`_as_the_loop_reads`).
What this kind has of its own:

* the comparison, `compare_lm_ssd_share` (the `ssd_scan` op alone, output
  and final state, and the convolution with its bias alone against the
  reference's token-by-token recurrence and shifted sums on the ops' own
  inputs; the first and the last mixer, the attention branch and an expert
  branch first-hand);
* `balance`: whether every expert layer's held share of the window's
  choices lies within the configuration's `reference.held_share_band` of
  the even share, in the result's `detail` and not in `correct` (the bias
  rule evens it over the warm-up; a load the seed tilts is a property of
  random routers, not a wrong result).
"""

import gc

from chipbench import compare_lm_ssd_share
from chipbench.kinds import train_tokens_window_share as window_kind
from chipbench.kinds.train_tokens import TokenSource, token_rows  # noqa: F401


def _as_the_loop_reads(cfg):
    """The configuration with its count of held experts also under the
    name `train_tokens_window_share._timed` reads it by."""
    return dict(cfg, num_experts=cfg["n_routed_experts"])


def run(ctx):
    from paddle_tpu import amp

    cfg = ctx.cfg
    if cfg.get("amp"):
        amp.enable(cfg["amp"])
    try:
        ctx.cfg = _as_the_loop_reads(cfg)
        res, rows, timed = window_kind._timed(ctx)
        ctx.cfg = cfg
        gc.collect()        # the timed program's scope, feeds and futures
        res["reference"] = ref = compare_lm_ssd_share.against_reference(
            ctx.fluid, cfg, ctx.builder, ctx.fluid.TPUPlace(0), ctx.seed,
            *rows, timed=timed)
        res["checks"] = dict(reference=bool(ref["ok"]), **res["checks"])
        res["correct"] = all(res["checks"].values())
        even = cfg["n_routed_experts"] \
            / cfg["deployment"]["n_routed_experts"]
        band = cfg["reference"]["held_share_band"]
        res["detail"]["balance"] = all(
            abs(s - even) <= band
            for s in res["detail"]["held_rows_share_by_layer"])
        return res
    finally:
        ctx.cfg = cfg
        amp.disable()
