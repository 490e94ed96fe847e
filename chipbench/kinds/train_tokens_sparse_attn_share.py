"""Traffic kind `train_tokens_sparse_attn_share`: K-step scans of the
training program of a language model that holds one chip's share of the
experts and of the vocabulary and whose every layer is grouped-query
attention OVER THE KEYS A LEARNED INDEXER PICKS for each query, the indexer
trained beside the model by a loss of its own (`keye_vl_2_0_30b_a3b`), on
packed rows of tokens resident on the device, dispatched one chunk ahead.
An item is a token.

The timed loop is `train_tokens_window_share._timed`, imported and not
copied, and with it `token_rows`, `TokenSource`, the `train` kind's
`run_chunks`, `timeline.train_reading` and `scopes.reduce_file`: build,
warm and time FIRST, compare AFTER the window on the window's own chunk 0
(so `setup_s` holds no comparison and `window_peak_bytes` is what the
traffic holds), the timed scan's losses of steps 0 and 1 held to the
reference, and those of step 0 and of the LAST step of its first chunk to
the comparison's second build run step by step through that chunk; and in
every step fetched: every token routed, the products took the held
experts' rows. That loop names a router bias and replays a rule over it:
this model has neither, its `expert_bias` is zero and its
`router_bias_update_speed` 0, so the replay holds that NOTHING wrote the
bias over every step the executable ran. What this kind has of its own:

* the comparison, `compare_lm_sparse_attn_share` (the selection's
  thresholds as the step wrote them, the selection as a selection, and
  the sparse attention branch, the head-mean probabilities and the
  indexer's loss of the first and of the last layer on the system's own
  choice);
* one more program counter, static, of the program the window times
  (`paddle_tpu.ops.lm_ops.lowered_counts`): the (query, key) pairs the
  selections keep a step against the causal pairs they choose among
  (`selected_pairs`, read by `dsa.selected_pairs_share`), of the program
  `_timed` built (`_KeepsProgram`);
* `balance`: whether every layer's held share of the window's choices lies
  within the configuration's `reference.held_share_band` of the even
  share, in the result's `detail` and not in `correct` (the model has no
  rule that evens the load; seeded routers tilt it).
"""

import gc
import types

from chipbench import compare_lm_sparse_attn_share
from chipbench.kinds import train_tokens_window_share as window_kind
from chipbench.kinds.train_tokens import TokenSource, token_rows  # noqa: F401


class _KeepsProgram:
    """The cell's builder, which remembers the first program it builds:
    the one `_timed` builds and the window times."""

    def __init__(self, builder):
        self._builder, self.prog = builder, None

    def __getattr__(self, name):
        return getattr(self._builder, name)

    def build(self, *args, **kw):
        built = self._builder.build(*args, **kw)
        if self.prog is None:
            self.prog = built["prog"]
        return built


def run(ctx):
    from paddle_tpu import amp

    cfg = ctx.cfg
    if cfg.get("amp"):
        amp.enable(cfg["amp"])
    try:
        from paddle_tpu.ops.lm_ops import lowered_counts

        builder = _KeepsProgram(ctx.builder)
        res, rows, timed = window_kind._timed(
            types.SimpleNamespace(**dict(vars(ctx), builder=builder)))
        gc.collect()        # the timed program's scope, feeds and futures
        # static, of the program the window timed (nothing is built again)
        counts = lowered_counts(builder.prog, ctx.devices[0])
        res["selected_pairs"] = {
            "selected": counts.get("sparse_attention_selected_pairs"),
            "causal": counts.get("sparse_attention_causal_pairs")}
        res["detail"]["lowered_counts"] = counts
        res["reference"] = ref = \
            compare_lm_sparse_attn_share.against_reference(
                ctx.fluid, cfg, ctx.builder, ctx.fluid.TPUPlace(0),
                ctx.seed, *rows, timed=timed)
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
