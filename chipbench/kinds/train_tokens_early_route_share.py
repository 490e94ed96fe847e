"""Traffic kind `train_tokens_early_route_share`: K-step scans of the
training program of a language model that holds one chip's share of each
layer, whose layers alternate between full attention without positions and
sliding-window attention, and WHOSE ROUTER READS THE LAYER'S INPUT
(`smallthinker_21b_a3b`), on packed rows of tokens resident on the device,
dispatched one chunk ahead. An item is a token.

The timed loop is `train_tokens_window_share._timed`, imported and not
copied, and with it `token_rows`, `TokenSource`, the `train` kind's
`run_chunks`, `timeline.train_reading` and `scopes.reduce_file`: build,
warm and time FIRST, compare AFTER the window on the window's own chunk 0
(so `setup_s` holds no comparison and `window_peak_bytes`, the reader
`early.peak_hbm_gb`, is what the traffic holds), the timed scan's losses
of steps 0 and 1 held to the reference, the first layer's router bias as
the scope holds it when the window closes held to the rule replayed over
the router counts of EVERY step the executable ran, and in every step
fetched: every token routed, the products took the held experts' rows.
That loop reads the counts of experts under the Laguna configuration's
key names; `_as_the_loop_reads` gives it this configuration's under those
names and changes nothing else. What this kind has of its own:

* the comparison, `compare_lm_early_route_share` (layer 0's choices, made
  from float32 embedding rows, must be the reference's; the router's
  gradient through `RouterInput`);
* the stand-in bias has a speed A LAYER (`optimizer.
  router_bias_update_speed_by_layer`: the routers' logits spread 30 x wider
  in layer 3 than in layer 0); the loop replays layer 0's, which is
  `optimizer.router_bias_update_speed`, and the comparison holds every
  layer's after its one step;
* `balance`: whether every layer's held share of the window's choices lies
  within the configuration's `reference.held_share_band` of the even
  share, in the result's `detail` and not in `correct` (a load that the
  seed tilts is a property of random routers, not a wrong result).
"""

import gc

from chipbench import compare_lm_early_route_share
from chipbench.kinds import train_tokens_window_share as window_kind
from chipbench.kinds.train_tokens import TokenSource, token_rows  # noqa: F401


def _as_the_loop_reads(cfg):
    """The configuration with its expert counts also under the names
    `train_tokens_window_share._timed` reads them by."""
    speeds = cfg["optimizer"]["router_bias_update_speed_by_layer"]
    assert speeds[0] == cfg["optimizer"]["router_bias_update_speed"]
    return dict(cfg,
                num_experts_per_tok=cfg["moe_num_active_primary_experts"],
                num_experts=cfg["moe_num_primary_experts"])


def run(ctx):
    from paddle_tpu import amp

    if ctx.cfg.get("amp"):
        amp.enable(ctx.cfg["amp"])
    cfg = ctx.cfg
    try:
        ctx.cfg = _as_the_loop_reads(cfg)
        res, rows, timed = window_kind._timed(ctx)
        ctx.cfg = cfg
        gc.collect()        # the timed program's scope, feeds and futures
        res["reference"] = ref = \
            compare_lm_early_route_share.against_reference(
                ctx.fluid, cfg, ctx.builder, ctx.fluid.TPUPlace(0), ctx.seed,
                *rows, timed=timed)
        res["checks"] = dict(reference=bool(ref["ok"]), **res["checks"])
        res["correct"] = all(res["checks"].values())
        even = cfg["moe_num_primary_experts"] \
            / cfg["deployment"]["moe_num_primary_experts"]
        band = cfg["reference"]["held_share_band"]
        res["detail"]["balance"] = all(
            abs(s - even) <= band
            for s in res["detail"]["held_rows_share_by_layer"])
        return res
    finally:
        ctx.cfg = cfg
        amp.disable()
