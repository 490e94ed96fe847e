"""The comparison that decides `correct` for a language model three of whose
four layers are GATED DELTANET (a 4-tap convolution, then the gated delta
rule's matrix state carried along the row) beside one output-gated
grouped-query attention layer at heads of 256, every layer with
softmax-routed held experts and a gated shared expert, that holds one
chip's SHARE of the experts and of the vocabulary (`qwen3_next_80b_a3b`):
the system under test against the configuration's plain float32 reference
(which is given the same share and computes the recurrence TOKEN BY
TOKEN), at the published widths, on the device the cell runs on, outside
the window, on the rows the cell's own window starts with. As in
`compare_lm_short_conv_share` (whose helpers, and `compare_lm`'s,
`compare_lm_share`'s, `compare_lm_window_share`'s and
`compare_lm_early_route_share`'s, this file imports, not copies) two
objects are set against the reference: (1) THE EXECUTABLE THE WINDOW TIMES,
its losses of steps 0 and 1 against the reference's first step and its
second after its own AdamW update, AND its losses of step 0 and of the
LAST step of its first chunk against the second build's own, run one step
at a time through that chunk (`TIMED_TWIN_TOL`, `TIMED_TWIN_LAST_TOL`: at
this depth and rate a step moves the loss by no more than the bf16 system
stands from the float32 reference, and by little more than two executables
of one program differ at step 1, so a carried state is told from one left
as it was at the chunk's last step, where what was not carried has grown
by a step's worth every step; PR 48); (2) a second build of the same program run step by
step with the gradients fetched, and its inference clone.

Compared on one row of 8192 tokens:

* THE OPS ALONE, first-hand, of the first and of the last delta layer:
  `gated_delta_rule`'s output o [T, 32 x 128] AND the state behind the
  row's last token [32, 128, 128] against the reference's token-by-token
  recurrence on the op's own inputs ([q | k | v] as the system's
  convolution wrote them, [b | a] as its projection did, the layer's A_log
  and dt_bias), AND THE SAME OP ONCE MORE IN FLOAT32 at full matmul
  precision on those inputs (what the op itself holds below float32:
  `delta_precision`, held by the MEDIAN AND THE UPPER QUARTILE OVER THE 32
  VALUE HEADS of a head's rms error, since as stated one or two heads
  carry the whole array's and a lowered precision lifts every head (PR
  48); a program of
  this check's own, not the timed step, through whose bf16 products no
  limit tells the state's precision);
  `short_conv` (gating "silu") against the reference's four
  shifted slices on the op's own input;
* FIRST-HAND BRANCHES: the delta branch of layer 0 and of layer 2 and the
  attention branch of layer 3 (16 query heads on 2 key/value heads of 256,
  per-head QK norm, partial rotary, the sigmoid gate), the system's output
  against the reference's ON THE SAME normed input;
* those inputs themselves against the reference's FROM THE TOKENS: the rms
  of their per-row scale error (the norm statistic);
* routing of the four layers (the ten chosen of 512), each judged on the
  tokens every layer before it routed alike: the share flipped, and every
  exchanged expert within `ROUTING_MARGIN` spreads of the reference's
  tenth logit;
* logits per token over the tokens routed alike everywhere; the loss; the
  global gradient norm and the clip's scale;
* gradient cosine, norm ratio and first AdamW update of a sampled parameter
  of each kind (`sampled_params`): W_qkvz, W_ba, the taps, A_log, dt_bias,
  the gated norm's scale and W_o of a delta layer (W_ba, the taps, A_log,
  dt_bias of the last one too), W_qg / W_k / W_v / W_o and both QK scales,
  two routers, the shared expert's gate w_s and one of its matrices, one
  held expert's three matrices, a norm scale, the table and the head;
* every layer's `DownOut`: its non-zero rows are `RowsHeld` = the choices
  on the held experts.

SINCE PR 56 THE REFERENCE IS ROUTED AS THE SYSTEM ROUTED: its one pass
(`reference_pass`) sends every token of every expert layer to the experts
the system's TRAINING step chose (`loss_and_grads(routing=)`), weighs them
by its own scores and returns its own free top-k beside. `routing` judges
the system's choices against those free ones, as choices (the flipped
share, every exchanged expert a neighbour of the threshold); the losses,
the global norm, every gradient and the pooled mixers are then read over
EVERY token on a reference that went where the system went, so a near-tie
that fell the other way under bf16 is judged once and not again in every
number behind it (a router's gradient is a sum over the few percent of a
row its held experts see: a handful of such tokens moved its norm by
percents, seed by seed, and refused the accepted program: PERF.md section
6, PR 56); the inference program's logits are read over the tokens it sent
where the training step sent them.

The limits, each from two readings: the largest the system gave as the
configuration states it over the builder's seeds on the chip ("stated"),
and the SYSTEM with one thing lowered or left out (`python -m
chipbench.lower_precision_lm_delta_share`, on the chip: the carried state
in bf16, g and the decays in bf16, g = 0, beta = 1, q and k not
normalised, the taps reversed, the output gate left out, the router in
bf16, the masters in bf16): every variant comes out not `correct` on the
study's seeds, `stated` correct. The readings stand beside each constant;
PERF.md section 6, PR 43.
"""

import gc
import time
from unittest import mock

import numpy as np

from chipbench import held
from chipbench.compare_lm import _clip_vars, _cos_ratio, _rel, _scalar
from chipbench.compare_lm_early_route_share import routing_report
from chipbench.compare_lm_share import _logits_errors as _errors_over
from chipbench.compare_lm_share import _products, routing_by_layer
from chipbench.compare_lm_window_share import _branch_errors

# THE RULE (PR 48), for every limit set again since: a limit stands at
# least a factor M above the standing program's worst reading over AT LEAST
# 24 SEEDS, and at least M below the least reading of every plant that this
# check ALONE is there to catch (a plant another check fails does not hold
# a limit down; where no plant reads against a number, the limit is said to
# be coarse: it holds a mechanism). Where no number has M on both sides the
# STATISTIC was changed, not only the number. The readings are committed,
# row by row, in `chipbench/data/limits_study.json`, and
# `chipbench/tests/test_limits_study.py` replays them against this file:
# every `stated` row passes, every plant row fails the check named for it,
# and M is what the file says. The 24 seeds: PR 47's study of the standing
# program (788aa83; nothing under `paddle_tpu/` changed since), four
# workers side by side, `chiprun_out/pr47/q24_*.jsonl`; 14 of them were
# `correct` under the limits as PR 43 / 44 left them. ONE EXCEPTION TO THE
# 24: `TIMED_TWIN_LAST_TOL` rests on the SEVEN seeds of this PR's own runs
# (no record before it followed the scan past step 1), and says so where
# it stands; it has 5.2 on either side, not 1.4.
M = 1.4
# READINGS (my chip runs, PR 43): "stated" = the largest (for a floor the
# smallest) the system gave as the configuration states it over its seeds
# (17: 14 runs of the cell and the study's 21, 22, 23) | the study's
# variants (`lower_precision_lm_delta_share`). WHICH SEEDS EACH VARIANT RAN
# ON, on the tree as committed (the program the cell runs, the faults
# planted from outside): `taps_reversed` and `state_bf16` 21, 22, 23;
# `g_bf16`, `router_bf16`, `no_decay`, `beta_one`, `no_qk_norm` 21, 22;
# `no_output_gate` 22; `masters` none (each seed's call ran into its time
# limit before it). On earlier trees: `state_bf16` and `g_bf16` (then g,
# beta AND the decays rounded) 15, 16, 17 and `router_bf16` 16 on the same
# program; all nine on seed 11 of the op's FIRST form (chunks of 64, the
# convolution as XLA lowers it), which is all `masters` has: AdamW and the
# attention's gate are the same code in both forms. A limit that both
# readings pass is said to be coarse: it holds a mechanism, not a precision. The first two runs of the cell were made
# under limits copied from the other share cells BEFORE any reading and
# failed three of them (`routing`, `logits`, `update`); the limits below
# are set from the readings.
# COARSE, and far above the other share cells' 0.14: TEN choices of 512
# with logits of std 0.9 put the tenth and the eleventh 0.04 std apart, and
# a bf16 state moves a logit by 0.003 - 0.01: stated 11.6% of the first
# layer's tokens change a choice, 26.8%, 42.0% and 43.3% of the later
# layers' (each judged on the tokens every layer before routed alike) |
# `router_bf16` 12.1, 22.4, 35.1, 35.7%: no precision shows here; the
# planted omissions read 100%. What says that a flip WAS a near-tie is the
# margin. PR 48, 24 seeds: stated worst 45.2% | the omissions 100%; M above
# the one is 0.633, M below the other 0.714
ROUTING_FLIP_MAX = 0.67
# COARSE: of the token's logit spread (std over the 512 experts): stated
# 0.246 | `router_bf16` 0.215; `no_output_gate` 1.42, `no_decay` 4.8.
# PR 48: over 24 seeds the stated worst is 0.429 (a widest gap swings by
# its nature: 0.45 left it 1.05); nearest plant 1.42; the geometric mean,
# 1.8 above the one and below the other
ROUTING_MARGIN = 0.78
# COARSE, over the tokens every layer routed alike (25 - 31% of the row:
# they still read the other tokens' states through three delta layers and
# an attention layer): stated 0.0805 max, 0.0411 rms | `router_bf16` 0.0515,
# 0.0325; `no_output_gate` 0.172, 0.101 (the geometric means of the two);
# the other omissions leave no token routed alike. PR 48, 24 seeds: the
# max reads up to 0.0885 (0.12 left it 1.36), the rms 0.0418 (1.55: kept);
# `no_output_gate` is `attention`'s to catch (0.92 against 0.012), so it
# does not hold this limit down, and 0.13 still lies under its 0.172
LOGITS_TOL = 0.13
LOGITS_RMS_TOL = 0.065
# the accepted share comparisons' limit, against the float32 REFERENCE:
# stated 1.0e-4 (the one step), 8.4e-5 (the timed scan's steps 0 and 1)
# over nine runs of the cell | `no_output_gate` 3.6e-4, `beta_one` 9.3e-4,
# `no_qk_norm` 4.6e-3; COARSE for the rest (`no_decay` 3.0e-4,
# `taps_reversed` 1.2e-4: on seeded weights the delta branch is a small
# part of the residual stream). It does NOT tell a second step that carried
# nothing: that reads 1.3e-4 - 2.3e-4 (at this depth and rate one step
# moves the loss by 2e-4, where the other share cells' read 1.2e-3 -
# 1.4e-3): `TIMED_TWIN_TOL` does
LOSS_TOL = 6e-4
# THE TIMED SCAN'S LOSSES OF STEPS 0 AND 1 AGAINST THE SECOND BUILD'S OWN of
# the same steps (the same program run one step at a time: the same seed's
# weights, the same rows, step 1 behind its own first update, which
# `update` holds to the reference's). Two executables of one program
# round alike but for their fusions: step 0 over eleven runs of the cell
# 1e-6 - 2.8e-5 (rms 1.4e-5), a third of what either stands from the float32
# reference; step 1, read in the two runs made with this limit (seeds
# 2147483311, 1234567891), 1.4e-6 and 5.0e-6 | a timed second step that
# carried nothing stands the whole step away, 1.3e-4 - 2.3e-4 (the
# reference's two second losses, the same eleven runs). The geometric mean
# of 2.8e-5 and 1.3e-4.
# PR 48: NO NUMBER HAS M ON BOTH SIDES AT STEP 1, SO THE STATISTIC CHANGED.
# Over the 24 seeds step 1 reads up to 9.14e-5 between two executables of
# one program (4 seeds over 6e-5: 7.59e-5, 7.90e-5, 8.28e-5, 9.14e-5; Adam's
# first step is +-rate a weight, so which near-tied tokens change expert
# at step 1 differs between them) where a second step that carried nothing
# reads from 1.13e-4, and seed by seed the ratio falls to 2.0. Step 0 (the
# same weights and rows) reads <= 8.08e-6 on all 24 and keeps this limit:
# it holds the timed executable's forward and its feed (half a batch left
# out reads the rows' spread, 1e-3 and more). What tells a carried state is
# now THE LAST STEP OF THE SCAN'S FIRST CHUNK (step K - 1 = 9) against the
# second build run K single steps: what was not carried grows by a step's
# 2e-4 every step, what two executables differ by does not
TIMED_TWIN_TOL = 6e-5
# the timed scan's loss of step K - 1 against the second build's own K-th
# single step | the same with NOTHING carried: the inference program's
# cross-entropy of step K - 1's rows at the weights as drawn (the same
# program's on step 0's rows stands within 4.8e-5 of the step's own loss:
# `err_unmoved_first_against_step_0`; a scan whose body returns its state
# unchanged reads it to four digits: `test_delta_cell.py`). SEVEN SEEDS,
# not 24: the records before this PR hold two steps (my chip runs, PR 48,
# `limits_study.json`: 1848000606, 1511168161, 2048000101, 1748000202,
# 1348000303, 948000404, 2147480505). Step 9: stated 8.3e-6 - 5.80e-5 |
# nothing carried 1.595e-3 - 2.197e-3, 27 times the worst sound reading;
# and what two executables differ by does NOT grow along the chunk: over
# those runs' 63 readings of steps 1 - 9 the worst is 7.2e-5 (step 4),
# inside step 1's 9.14e-5 over the 24 seeds, which therefore stands in
# for this reading's tail. The geometric mean of 5.8e-5 and 1.6e-3: 5.2
# from either, 3.3 above step 1's worst of 24. Set for the traffic's K = 10
TIMED_TWIN_LAST_TOL = 3e-4
# stated 4.6e-4 | `taps_reversed` 4.5e-3, `no_decay` 1.3e-2,
# `no_output_gate` 0.23; COARSE for `router_bf16` (6.4e-4)
GLOBAL_NORM_TOL = 2e-3
# stated 4.4e-8
CLIP_SCALE_TOL = 1e-5
# stated 0.059 (a norm scale: a step of 1e-6 is 8.4 float32 ulps of 1.0;
# every matrix <= 0.007) | `masters` 15585 (a bf16 master cannot hold the
# step)
UPDATE_TOL = 0.1
# A_log and dt_bias: a step of 1e-6 is TWO float32 ulps of a value between 4
# and 8 (dt_bias lies in [-6.9, -2.3], A_log up to 2.8), so rounding alone
# reads up to a quarter: stated 0.113 | `masters` 1.0 and more
UPDATE_TOL_GATE_SCALARS = 0.3
# THE OP AS THE CELL RUNS IT: `gated_delta_rule`'s output and final state
# against the token-by-token recurrence of the op's own inputs, rms error
# over the reference's rms, both delta layers: stated 0.00463 (o), 0.00371
# (the state) | `no_decay` 3.2 / 3.4, `beta_one` 1.0 / 1.0, `no_qk_norm`
# 0.95 / 0.77: THE FORM. The bf16 operands of the chunk products make the
# stated reading; a precision inside does not move it (next)
DELTA_OP_RMS_TOL = 0.008
DELTA_STATE_RMS_TOL = 0.008
# WHAT THE OP HOLDS IN FLOAT32. On seeded weights the state forgets within
# a few chunks (A up to 16) and the bf16 operands of the chunk products make
# the reading above, so a state carried in bf16 reads 0.0039 - 0.0042 where
# float32 reads 0.0033 - 0.0037, and g and the decays in bf16 the same; with
# the decay slowed 100 and 10,000 times (a state that remembers the whole
# row) 0.0051 - 0.0054 against 0.0037 - 0.0044: no limit to stand on. So the
# same lowering is run once more on the op's own inputs IN FLOAT32 AT FULL
# MATMUL PRECISION: what is then left below float32 is what the op itself
# holds below float32, as stated nothing, and the planted precisions stand
# alone. (The triangular inverse's three bf16 passes are stated; left in,
# they read 3e-5 to 5.6e-4 over eight seeds by the chunks' conditioning, as
# much as a bf16 state: the probe forms the inverse at HIGHEST.) Readings
# with the inverse's passes in: output, stated 3e-5 - 4.5e-4 | `state_bf16`
# 5.4e-4 - 1.65e-3, `g_bf16` 2.2e-3 - 2.8e-3; final state, stated 3e-5 -
# 5.6e-4 | `state_bf16` 1.67e-3 - 2.18e-3, `g_bf16` 2.1e-3 - 3.1e-3; with
# the inverse at HIGHEST (seeds 2147481999, 17): output, stated 1.4e-4 |
# `state_bf16` 8.8e-4, `g_bf16` 2.3e-3; final state, stated 1.5e-4 |
# `state_bf16` 1.71e-3, `g_bf16` 2.2e-3 (what is left as stated is the
# chip's float32 exp and rsqrt over 64 chunks). The study as committed
# (seeds 21, 22, 23; `g_bf16` = g and beta rounded in `gates`, the decays
# formed from them unrounded): output, stated 2.9e-5 - 1.34e-4 |
# `state_bf16` 5.7e-4 - 1.08e-3, `g_bf16` 1.52e-3 - 1.73e-3; final state,
# stated 2.7e-5 - 1.44e-4 | `state_bf16` 1.66e-3 - 1.83e-3, `g_bf16`
# 1.55e-3 - 1.75e-3; the two limits are held TOGETHER, the state's has the
# room.
# PR 48: OVER 24 SEEDS THE WHOLE ARRAY'S RMS HAS NO LIMIT WITH ROOM: stated
# reads 1.95e-5 - 6.01e-4 (output) and 1.59e-5 - 8.27e-4 (final state), six
# seeds over the old 4e-4 / 5e-4, against `state_bf16` from 5.08e-4 / 1.66e-3
# and `g_bf16` from 1.45e-3 / 1.53e-3 (seeds 1133040875, 2147480046,
# 1765400321, 1511168161, through the kernels). BY VALUE HEAD the cause
# shows: as stated ONE OR TWO of the 32 heads carry the whole reading (seed
# 1511168161, first delta layer: head 14 reads 9.7e-4, 27 heads under
# 2e-5; which head, and how far, goes with the seed's A_log / dt_bias
# draw and the row's chunks), where a bf16 state or bf16 gates lift EVERY
# head. So the precision is held by THE MEDIAN OVER THE 32 HEADS of a
# head's rms error over that head's own reference rms, worst delta layer:
# output, stated <= 1.29e-5 (24 seeds) | `state_bf16` >= 2.10e-4, `g_bf16`
# >= 1.55e-3: the geometric mean, 3.9 from either; final state, stated
# <= 1.35e-5 | `g_bf16` >= 1.55e-3, `state_bf16` >= 1.65e-3: 10 from
# either
DELTA_F32_OP_HEAD_MEDIAN_TOL = 5e-5
DELTA_F32_STATE_HEAD_MEDIAN_TOL = 1.4e-4
# A MEDIAN PASSES A LOWERED PRECISION IN UP TO 15 OF THE 32 HEADS (and the
# whole array's bounds below lie above what `state_bf16` reads there), so
# THE UPPER QUARTILE over the heads (`np.quantile(.., 0.75)`, worst delta
# layer) is held beside it: nine heads lifted lift it. The same 30 seeds
# and 10 plant rows (`limits_study.json`): output, stated 1.55e-5 - 4.49e-5
# (as stated at most 4 heads of a layer read over 1e-4, the quartile's 8
# never) | `state_bf16` >= 5.25e-4, `g_bf16` >= 1.72e-3: the geometric
# mean, 3.4 from either; final state, stated <= 4.34e-5 | `state_bf16` >=
# 1.676e-3, `g_bf16` >= 1.83e-3: 6.2 from either. What up to eight heads
# hold below float32 is left to the whole array's bounds
DELTA_F32_OP_HEAD_QUARTILE_TOL = 1.5e-4
DELTA_F32_STATE_HEAD_QUARTILE_TOL = 2.7e-4
# and the whole array's rms stays as a COARSE bound, three times the stated
# worst of the 24 (6.01e-4, 8.27e-4; this PR's six fresh seeds read up to
# 6.81e-4, 9.55e-4: a fresh seed read higher than two dozen had, which is
# why no limit 1.4 above them would have stood): a fault in a few heads,
# which a median passes; the planted precisions read inside it and are the
# medians' to catch
DELTA_F32_OP_RMS_TOL = 1.8e-3
DELTA_F32_STATE_RMS_TOL = 2.5e-3
# `short_conv` (gating "silu") against four shifted slices of the op's own
# input: one bf16 rounding of the output as stated: 0.00166 (every reading)
# | `taps_reversed` 1.39 - 1.41 (seeds 21, 22, 23, the kernels' taps reversed
# with the plain form's: on a TPU place the kernels are what runs)
CONV_OP_RMS_TOL = 0.0025
# the delta branch and the attention branch, first-hand, max and rms error
# over the branch's largest element and rms: the bf16 products around the
# ops make most of it. Delta: stated 0.0090, 0.0085 | `no_decay` 2.4,
# `beta_one` 0.77, `no_qk_norm` 0.92, `taps_reversed` 1.49. Attention:
# stated 0.0045, 0.0051 | `no_output_gate` 0.92, 1.00
# PR 48, 24 seeds: the delta branch's rms reads up to 0.00865 (0.012 left
# it 1.39), its max 0.0098 (2.04: kept) | the plants from 0.77
DELTA_TOL = 0.02
DELTA_RMS_TOL = 0.013
ATTENTION_TOL = 0.012
ATTENTION_RMS_TOL = 0.010
# COARSE for a precision (no variant lowers the norms' statistics here; the
# LFM2 study did): the per-row scale error of the operators' normed inputs.
# The first delta layer's input is the norm of the float32 embedding: stated
# 0.0; the others lie behind bf16 layers and expert layers whose flipped
# tokens arrive as other tokens: stated 8.3e-4 (the last delta layer), 1.35e-3
# (attention; 1.0e-3 and 1.7e-3 on a fifth seed) | `no_decay` 0.76, `beta_one` 0.29, `taps_reversed` 0.99
NORM_SCALE_TOL = {"delta_first": 1e-5, "delta_last": 2.5e-3,
                  "attention": 4e-3}
# gradient cosine at least, norm ratio within, by kind of parameter, over
# seven seeds. The two routers: stated 0.9894 (a delta layer's), 0.9625 (the
# attention layer's, behind every flip), ratio 0.6% | `router_bf16` 0.156,
# 16% / 0.103 (seed 11); -0.046 / 0.218 and -0.018 (seeds 21, 22). The held expert (gate, up, down): stated 0.9769, 0.8% | 0.12
# and less. A_log, dt_bias (32 numbers each, sums over 8192 tokens of
# exponentials; their norm is the noisiest number here): stated 0.9958,
# ratio 5.2% | `no_output_gate` 0.94, 20%; COARSE for `router_bf16` (0.975,
# 4.7%)
# PR 56, THE ROUTERS' AND THE HELD EXPERT'S LIMITS SET AGAIN ON THE ROUTED
# REFERENCE. Against the plain reference, whose later layers send 22 - 41%
# of their tokens elsewhere, the same six runs of the census read 1 - cos up
# to 0.0110 (a delta layer's router), 0.0334 (the attention layer's, behind
# every flip) and 0.0238 (the expert), 2.1 - 2.7 times under their limits:
# the tail that a check of fourteen fresh seeds a side finds now and then.
# Sent where the system went (`limits_study.json`, the routed rows, six
# seeds): the routers 1 - cos <= 1.31e-3 and 1.64e-3, ratio <= 0.0018; the
# expert 1 - cos <= 1.32e-3, ratio <= 0.0050 | `router_bf16` on the routed
# reference too (seed 1906508178): the routers' 1 - cos 0.944 and 0.901, the
# expert's 0.300 - 0.332 (plain rows before: cosines of 0.156, 0.103, 0.12
# and less; a cosine that far never hung on a flip), and every parameter
# behind them past its own limit. Were (0.97, 0.06), (0.93, 0.06)
# and (0.95, 0.05): each now stands six times and more over the worst of the
# six (so few seeds: not the 1.4 of a census of 24) and far under the plant
GRAD_LIMITS = {"router": (0.99, 0.012), "router_attn": (0.99, 0.012),
               "expert": (0.99, 0.03),
               "A_log": (0.99, 0.12), "dt_bias": (0.99, 0.12),
               "A_log_last": (0.99, 0.12), "dt_bias_last": (0.99, 0.12)}
# every other sampled parameter: stated 0.99507 (W_k; W_qg 0.99531, the
# gated norm's scale 0.99697, the taps 0.99738), ratio 1.4% | `router_bf16`
# 0.968 (the taps), 0.980 (w_s); `no_output_gate` 0.91 - 0.96
GRAD_LIMITS_ELSE = (0.988, 0.03)
P = "qwen3next."
FIRST_HAND = ("delta_first", "delta_last", "attention")
DELTA_LAYERS = ("delta_first", "delta_last")


def _logits_errors(got, ref, same):
    if not same.any():
        return float("inf"), float("inf")
    return _errors_over(got, ref, same)


def system_side(fluid, cfg, builder, place, seed, tokens, labels,
                then=None):
    """What the system computes on the row, as numpy: the weights the
    startup program drew (`w0`, every parameter), the inference program's
    logits, routing, the operator branches (input, output) of `FIRST_HAND`
    and the two delta layers' own ops (the convolution's input and output,
    [b | a], the recurrence's output and last state), the training step's
    loss, routing, global norm, clip scale, clipped gradients and updated
    weights of the sampled parameters; with `then` = (tokens, labels) of
    the steps behind the first, [n x rows, S], each step's loss behind the
    one before (`losses_next`; `loss_next` the first of them) and, before
    any step, the inference program's own cross-entropy of the LAST of
    those steps' rows at the weights as drawn (`loss_unmoved_last`: what a
    scan that never carried its state would read there; `loss_unmoved_first`
    is the same program's on the first step's rows, which the step's own
    loss checks). Its scope is gone when this returns."""
    built = builder.build(fluid, cfg, seed, for_compare=True)
    picks = builder.sampled_params(cfg)
    at = builder.first_hand_layers(cfg)
    gnorm_var, scale_var = _clip_vars(built["prog"])
    feed = {built["token_feed"]: tokens, built["label_feed"]: labels}
    ids_vars = [r[0] for r in built["routing"]]
    branches = [v for k in FIRST_HAND for v in built["operators"][at[k]][1:]]
    own = [v for k in DELTA_LAYERS for v in built["delta_ops"][at[k]]]
    products = _products(built["test_prog"])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(built["startup"])
        w0 = {p.name: np.asarray(scope.find_var(p.name), np.float32)
              for p in built["prog"].global_block().all_parameters()}
        eval_fetches = [built["logits"]] + branches + ids_vars + own \
            + [n for pair in products for n in pair]
        evaled = exe.run(built["test_prog"], feed=feed,
                         fetch_list=eval_fetches)
        n_ids = 1 + len(branches) + len(ids_vars)
        own_got = [np.asarray(v, np.float32)
                   for v in evaled[n_ids:n_ids + len(own)]]
        del evaled[n_ids:n_ids + len(own)]
        rows_written = [
            (int(np.any(np.asarray(down) != 0, axis=1).sum()),
             int(np.asarray(held).reshape(-1)[0]))
            for down, held in zip(evaled[n_ids::2], evaled[n_ids + 1::2])]
        evaled = evaled[:n_ids]
        rows = len(tokens)
        behind = [] if then is None else [
            (then[0][i:i + rows], then[1][i:i + rows])
            for i in range(0, len(then[0]), rows)]
        unmoved = [None, None]
        if behind:
            # the weights as drawn on the LAST step's rows: the same
            # inference executable (the same fetches) once more
            unmoved = [_cross_entropy(evaled[0], labels), _cross_entropy(
                exe.run(built["test_prog"], fetch_list=eval_fetches,
                        feed={built["token_feed"]: behind[-1][0],
                              built["label_feed"]: behind[-1][1]})[0],
                behind[-1][1])]
        step_fetches = [built["loss"], gnorm_var, scale_var] + ids_vars \
            + [n + "@GRAD_clipped" for n in picks.values()]
        fetched = exe.run(built["prog"], feed=feed, fetch_list=step_fetches)
        w1 = {k: np.asarray(scope.find_var(n)).astype(np.float32)
              for k, n in picks.items()}
        # the same executable once more a step: each behind the one before
        losses_next = [_scalar(exe.run(
            built["prog"], fetch_list=step_fetches,
            feed={built["token_feed"]: t, built["label_feed"]: l})[0])
            for t, l in behind]
    delta_ops = {k: tuple(own_got[5 * i:5 * i + 5])
                 for i, k in enumerate(DELTA_LAYERS)}
    exact = delta_ops_in_float32(built["test_prog"], w0, at, delta_ops)
    n_layers, n_b = len(ids_vars), len(branches)
    got = dict(zip(("loss", "gnorm", "scale"),
                   (_scalar(v) for v in fetched[:3])))
    got.update(
        loss_next=losses_next[0] if losses_next else None,
        losses_next=losses_next, loss_unmoved_first=unmoved[0],
        loss_unmoved_last=unmoved[1], w0=w0, w1=w1, logits=np.asarray(evaled[0], np.float32),
        operators={k: (np.asarray(u, np.float32), np.asarray(o, np.float32))
                   for k, u, o in zip(FIRST_HAND, evaled[1:1 + n_b:2],
                                      evaled[2:1 + n_b:2])},
        # (conv in, conv out, [b | a], o, last state) a delta layer
        delta_ops=delta_ops, delta_ops_float32=exact,
        ids_eval=[np.asarray(v) for v in evaled[1 + n_b:]],
        rows_written=rows_written,
        ids=[np.asarray(v) for v in fetched[3:3 + n_layers]],
        clipped={k: np.asarray(v).astype(np.float32)
                 for k, v in zip(picks, fetched[3 + n_layers:])})
    del scope, exe, fetched, evaled, built
    gc.collect()
    return got


def _cross_entropy(logits, labels):
    """Mean cross-entropy of `labels` [rows, S] under `logits` [T, V], in
    float64 on the host, a block of rows at a time."""
    logits = np.asarray(logits).reshape(labels.size, -1)
    labels = np.asarray(labels).reshape(-1)
    total = 0.0
    for i in range(0, labels.size, 1024):
        z = logits[i:i + 1024].astype(np.float64)
        z -= z.max(axis=1, keepdims=True)
        total += float((np.log(np.exp(z).sum(axis=1)) - z[
            np.arange(len(z)), labels[i:i + 1024]]).sum())
    return total / labels.size


def _by_head(got, ref, heads):
    """rms error over the reference's rms, a value head at a time: of the
    op's output [T, heads x dv] or of a state [rows, heads, dk, dv]."""
    diff = (got.astype(np.float64) - ref).reshape(len(got), heads, -1)
    ref = ref.astype(np.float64).reshape(len(ref), heads, -1)
    return (np.sqrt(np.mean(diff ** 2, axis=(0, 2)))
            / np.sqrt(np.mean(ref ** 2, axis=(0, 2)))).tolist()


def delta_ops_in_float32(prog, w0, at, delta_ops):
    """{what: (output [T, Hv dv], last state) of the SYSTEM's
    `gated_delta_rule` op run alone on that layer's own [q | k | v] and
    [b | a], as float32 arrays under full matmul precision}: the op's
    registered kernel function under the program's own attrs (so a cast in
    the op's wrapper shows), every product exact, so that only what the
    lowering itself holds below float32 is left. It is a program of this
    check's own and NOT the timed step: under AMP the step hands the op
    bf16 [q | k | v], whose products' rounding hides the state's."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.lm_ops import gated_delta_rule_op
    from paddle_tpu.parallel import delta_rule

    ops = {op.input("ALog")[0]: op for op in prog.global_block().ops
           if op.type == "gated_delta_rule"}
    found = {}

    def alone(op):
        def run(qkv, ba, a_log, dt_bias):
            res = gated_delta_rule_op(None, {
                "QKV": [qkv], "BA": [ba], "ALog": [a_log],
                "DtBias": [dt_bias]}, op.attrs)
            return res["Out"][0], res["FinalState"][0]

        return jax.jit(run)

    # the triangular inverse's three bf16 passes are stated, and read 3e-5
    # to 4e-4 here by the chunk's conditioning: taken out of the probe too
    with jax.default_matmul_precision("highest"), mock.patch.object(
            delta_rule, "INVERSE_PRECISION", jax.lax.Precision.HIGHEST):
        for k, (_, mixed, ba, _, _) in delta_ops.items():
            p = f"{P}l{at[k]}."
            out, last = alone(ops[p + "A_log"])(
                *(jnp.asarray(x, jnp.float32) for x in (
                    mixed, ba, w0[p + "A_log"], w0[p + "dt_bias"])))
            found[k] = (np.asarray(out), np.asarray(last))
    return found


def _layer_weights(w0, i):
    import jax.numpy as jnp

    return {k: jnp.asarray(v) for k, v in w0.items()
            if k.startswith(f"{P}l{i}.")}


def reference_branches(cfg, builder, w0, tokens, inputs):
    """{what: the reference's operator branch of that first-hand layer on
    the normed input the system itself fed its own, [T, C]}."""
    import jax.numpy as jnp

    at = builder.first_hand_layers(cfg)
    return {k: np.asarray(builder.reference.operator_branch(
        cfg, _layer_weights(w0, at[k]), at[k],
        jnp.asarray(inputs[k]).reshape(tokens.shape + (-1,)))).reshape(
            tokens.size, -1) for k in FIRST_HAND}


def own_inputs(got):
    """What the first-hand checks hand the reference: {what: the normed
    input the system fed its operator branch}, {what: (the input of its
    `short_conv` op, the [q | k | v] and [b | a] its `gated_delta_rule` op
    read)}."""
    return ({k: u for k, (u, _) in got["operators"].items()},
            {k: (x, mixed, ba) for k, (x, mixed, ba, _, _)
             in got["delta_ops"].items()})


def reference_delta_ops(cfg, builder, w0, tokens, op_inputs):
    """{what: (the reference's silu(conv4) of the input the system's
    `short_conv` read, the token-by-token recurrence's output [T, Hv dv]
    and last state [rows, Hv, dk, dv] on the inputs the system's
    `gated_delta_rule` read)}."""
    import jax
    import jax.numpy as jnp

    ref = builder.reference
    at = builder.first_hand_layers(cfg)
    found = {}

    def ops(w, p, x, mixed, ba):
        o, last = ref.delta_rule(*ref.delta_inputs(mixed, ba, w, p, cfg))
        return ref.silu_conv(x, w[p + "conv_taps"]), o, last

    with jax.default_matmul_precision(ref.PRECISION):
        for k, arrays in op_inputs.items():
            p = f"{P}l{at[k]}."
            conv, o, last = jax.jit(lambda w, *a, p=p: ops(w, p, *a))(
                _layer_weights(w0, at[k]), *(
                    jnp.asarray(a).reshape(tokens.shape + (-1,))
                    for a in arrays))
            found[k] = (np.asarray(conv).reshape(tokens.size, -1),
                        np.asarray(o).reshape(tokens.size, -1),
                        np.asarray(last))
    return found


def reference_inputs(cfg, builder, w0, tokens, sent=None):
    """{what: the normed input of that first-hand layer's operator as the
    reference computes it FROM THE TOKENS, [T, C]}; `sent`: the experts
    each layer sends its tokens to (`reference_pass`)."""
    import jax
    import jax.numpy as jnp

    ref, eps = builder.reference, cfg["rms_norm_eps"]
    at = builder.first_hand_layers(cfg)
    last = max(at.values())
    kinds = ref.layer_kinds(cfg)
    before = (P + "embed",) + tuple(f"{P}l{i}." for i in range(last + 1))
    w = {k: jnp.asarray(v) for k, v in w0.items() if k.startswith(before)}

    def inputs(w_, t, sent_):
        x, found = w_[P + "embed"][t], {}
        for i in range(last + 1):
            found[i] = ref.rms_norm(x, w_[f"{P}l{i}.operator_norm"], eps)
            if i < last:
                x, _ = jax.checkpoint(
                    lambda x_, w__, c_, i=i: ref.layer(
                        x_, w__, i, kinds[i], cfg, c_))(
                            x, w_, None if sent_ is None else sent_[i])
        return [found[at[k]] for k in FIRST_HAND]

    with jax.default_matmul_precision(ref.PRECISION):
        return {k: np.asarray(u).reshape(tokens.size, -1)
                for k, u in zip(FIRST_HAND, jax.jit(inputs)(
                    w, jnp.asarray(tokens), None if sent is None
                    else [jnp.asarray(v) for v in sent]))}


def reference_second_step(cfg, builder, wj, grads, tokens, labels):
    """The reference's loss on the rows of step 1 after ITS OWN first step
    (the first AdamW update of every trained weight behind the global
    clip), and the loss on the same rows had the first step left the state
    as it was: (loss, loss with nothing carried)."""
    import jax
    import jax.numpy as jnp

    ref, o = builder.reference, cfg["optimizer"]
    delta, _ = ref.adamw_first_update(
        cfg, wj, grads, epsilon=o["epsilon"] / np.sqrt(1.0 - o["beta2"]))
    w1 = dict(wj)
    for name in list(delta):
        w1[name] = wj[name] + delta.pop(name)
    with jax.default_matmul_precision(ref.PRECISION):
        loss = jax.jit(lambda w_, t, l: ref.loss_fn(cfg, w_, t, l)[0])
        t, l = jnp.asarray(tokens), jnp.asarray(labels)
        return float(loss(w1, t, l)), float(loss(wj, t, l))


def reference_pass(cfg, builder, w0, tokens, labels, sent=None):
    """The reference's pass over the rows, as numpy: the first step's loss,
    logits, its own free choices (`routing`), the gradients asked for, the
    operators' normed inputs FROM THE TOKENS and, where `tokens` holds the
    rows of a second step behind those of the first
    (`cfg["reference"]["rows"]`), `reference_second_step`. With `sent` (the
    expert ids [T, k] the system's training step chose, a layer) the first
    step is ROUTED AS THE SYSTEM ROUTED (`loss_and_grads(routing=)`); None:
    the plain reference."""
    import jax.numpy as jnp

    ref, picks = builder.reference, builder.sampled_params(cfg)
    rows = int(cfg["reference"]["rows"])
    first, then = (tokens[:rows], labels[:rows]), (tokens[rows:2 * rows],
                                                    labels[rows:2 * rows])
    wj = {k: jnp.asarray(v) for k, v in w0.items()}
    t0, l0 = jnp.asarray(first[0]), jnp.asarray(first[1])
    loss, (logits, routing), grads = ref.loss_and_grads(
        cfg, wj, t0, l0,
        routing=None if sent is None else [jnp.asarray(v) for v in sent])
    gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values())))
    T = first[0].size
    side = dict(
        loss=float(loss), gnorm=gnorm, sent=sent,
        routing=[(np.asarray(b), np.asarray(t)) for b, t in routing],
        logits=np.asarray(logits).reshape(T, -1),
        grads={k: np.asarray(grads[n]) for k, n in picks.items()})
    del logits
    if len(then[0]):
        side["second_step"] = reference_second_step(cfg, builder, wj, grads,
                                                    *then)
    del grads, wj
    side["operator_inputs"] = reference_inputs(cfg, builder, w0, first[0],
                                               sent)
    return side


def reference_side(cfg, builder, w0, tokens, labels, inputs, op_inputs,
                   sent=None):
    """`reference_pass` and, first-hand on the system's `own_inputs`
    (`inputs`, `op_inputs`), the operator branches and the delta layers'
    own ops."""
    side = reference_pass(cfg, builder, w0, tokens, labels, sent)
    rows = int(cfg["reference"]["rows"])
    side["operators"] = reference_branches(cfg, builder, w0, tokens[:rows],
                                           inputs)
    side["delta_ops"] = reference_delta_ops(cfg, builder, w0, tokens[:rows],
                                            op_inputs)
    return side


def reference_of(cfg, builder, got, tokens, labels, routed=True,
                 whole=True):
    """The reference for the system side `got`: sent where its training
    step's experts went (`routed`) or plain; `whole`: with the first-hand
    parts on the system's `own_inputs`, else the pass alone (one signature
    in the three share comparisons: `chipbench.census` reads every seed
    both ways)."""
    sent = got["ids"] if routed else None
    if not whole:
        return reference_pass(cfg, builder, got["w0"], tokens, labels, sent)
    return reference_side(cfg, builder, got["w0"], tokens, labels,
                          *own_inputs(got), sent=sent)


def _routing_by_layer(ids, routing_ref, sent=None):
    return routing_by_layer(routing_report, ROUTING_MARGIN, ids,
                            routing_ref, sent)


def judge(cfg, builder, got, ref, timed=None):
    """The report: every number, the limits, which of them `failed`.
    `timed`: {"losses": the losses of steps 0 and 1 as the TIMED
    executable fetched them}, where `ref` holds a second step."""
    picks = builder.sampled_params(cfg)
    # the reference went where the TRAINING step went (`sent`): the
    # inference program's choices and logits compare where it went there too
    route, _ = _routing_by_layer(got["ids"], ref["routing"], ref.get("sent"))
    route_eval, same = _routing_by_layer(got["ids_eval"], ref["routing"],
                                         ref.get("sent"))
    main_max, main_rms = _logits_errors(got["logits"], ref["logits"], same)
    first = cfg["deployment"]["first_expert"]
    held_n, n_all = cfg["num_experts"], cfg["deployment"]["num_experts"]
    last_delta = builder.first_hand_layers(cfg)["delta_last"]
    counts = np.bincount(ref["routing"][last_delta][1].ravel(),
                         minlength=n_all)
    expert = int(counts[first:first + held_n].argmax())
    rows = [[written, held, int(((ids >= first)
                                 & (ids < first + held_n)).sum())]
            for (written, held), ids in zip(got["rows_written"],
                                            got["ids_eval"])]
    o = cfg["optimizer"]
    eps = o["epsilon"] / np.sqrt(1.0 - o["beta2"])
    by_param = {}
    for key, name in picks.items():
        g_hat, g_ref = got["clipped"][key], ref["grads"][key]
        a, b = got["w0"][name], got["w1"][key]
        if key.startswith("expert_"):
            g_hat, g_ref, a, b = (v[expert] for v in (g_hat, g_ref, a, b))
        cos, ratio = _cos_ratio(g_hat / got["scale"], g_ref)
        decay = o["weight_decay"] if builder.reference.decays(name) else 0.0
        want = -o["learning_rate"] * (g_hat / (np.abs(g_hat) + eps)
                                      + decay * a)
        cos_min, ratio_tol = _grad_limits(key)
        by_param[key] = {
            "grad_cos": cos, "grad_norm_ratio": ratio,
            "grad_ok": bool(cos is not None and cos >= cos_min
                            and abs(ratio - 1.0) <= ratio_tol),
            "update_err": float(np.abs((b - a) - want).max()
                                / max(np.abs(want).max(), 1e-30))}
    operators = {k: _branch_errors(got["operators"][k][1],
                                   ref["operators"][k]) for k in FIRST_HAND}
    conv_ops, delta_ops, delta_states, exact_ops, exact_states = ({}, {}, {},
                                                                  {}, {})
    by_head, heads = {}, int(cfg["linear_num_value_heads"])
    for k in DELTA_LAYERS:
        _, mixed, _, out, last = got["delta_ops"][k]
        conv_ref, out_ref, last_ref = ref["delta_ops"][k]
        conv_ops[k] = _branch_errors(mixed, conv_ref)
        delta_ops[k] = _branch_errors(out, out_ref)
        delta_states[k] = _branch_errors(last, last_ref)
        exact_ops[k] = _branch_errors(got["delta_ops_float32"][k][0], out_ref)
        exact_states[k] = _branch_errors(got["delta_ops_float32"][k][1],
                                         last_ref)
        by_head[k] = {
            "op_by_head": _by_head(got["delta_ops_float32"][k][0], out_ref,
                                   heads),
            "state_by_head": _by_head(got["delta_ops_float32"][k][1],
                                      last_ref, heads)}
    inputs = {}
    for k in FIRST_HAND:
        y, y_ref = got["operators"][k][0], ref["operator_inputs"][k]
        row_scale = np.sum(y * y_ref, axis=1) / np.sum(y_ref * y_ref, axis=1)
        inputs[k] = (_branch_errors(y, y_ref)[1],
                     float(np.sqrt(np.mean(np.square(row_scale - 1.0)))))
    steps = {}
    if timed is not None and "second_step" in ref:
        after, unmoved = ref["second_step"]
        steps = {"loss_timed_reference": [
                     [float(timed["losses"][0]), ref["loss"]],
                     [float(timed["losses"][1]), after]],
                 "second_loss_had_nothing_carried": unmoved}
        steps["err"] = [_rel(a, b) for a, b in steps["loss_timed_reference"]]
        steps["err_had_nothing_carried"] = _rel(unmoved, after)
        # the second build's own steps, as many as it was run, against
        # the timed scan's first chunk
        own = [got["loss"]] + list(got.get("losses_next") or (
            [got["loss_next"]] if got.get("loss_next") is not None else []))
        scan = [float(v) for v in timed.get("chunk_losses",
                                            timed["losses"])][:len(own)]
        steps["loss_second_build"] = own
        steps["loss_timed"] = scan
        steps["err_second_build"] = [_rel(t, o) for t, o in zip(scan, own)]
        if len(own) > 1 and got.get("loss_unmoved_last") is not None:
            steps["last_step"] = len(own) - 1
            steps["err_second_build_last"] = steps["err_second_build"][-1]
            # what a scan that never carried its state would read at its
            # last step: the weights as drawn, on that step's rows
            steps["loss_unmoved_first_last"] = [got["loss_unmoved_first"],
                                                got["loss_unmoved_last"]]
            steps["err_last_had_nothing_carried"] = _rel(
                got["loss_unmoved_last"], own[-1])
            # the host's cross-entropy of the inference program's logits
            # against the step's own loss, both at the weights as drawn
            steps["err_unmoved_first_against_step_0"] = _rel(
                got["loss_unmoved_first"], own[0])
    report = {
        "operator_branch_err_max_rms": operators,
        "delta_rule_op_err_max_rms": delta_ops,
        "delta_rule_final_state_err_max_rms": delta_states,
        "delta_rule_in_float32_op_err_max_rms": exact_ops,
        "delta_rule_in_float32_final_state_err_max_rms": exact_states,
        "delta_rule_in_float32_err_rms_by_part": by_head,
        "conv_op_err_max_rms": conv_ops,
        "operator_input_err_rms_rowscale": inputs,
        "timed_steps": steps,
        "product_rows_written_held_chosen": rows,
        "config": cfg["name"], "rows": int(cfg["reference"]["rows"]),
        "expert": first + expert, "reference": cfg["reference"]["file"],
        "reference_routed_as_the_system": ref.get("sent") is not None,
        # each layer's routing judged on the tokens sent, so far, where the
        # reference went (`_routing_by_layer(sent=)`)
        "routing_judged_where_sent": True,
        "routing": route, "routing_inference": route_eval,
        "tokens_routed_alike_everywhere": float(same.mean()),
        "logits_err_max": main_max, "logits_err_rms": main_rms,
        "train_loss": [got["loss"], ref["loss"]],
        "train_loss_err": _rel(got["loss"], ref["loss"]),
        "global_grad_norm": [got["gnorm"], ref["gnorm"]],
        "global_grad_norm_err": _rel(got["gnorm"], ref["gnorm"]),
        "clip_scale": got["scale"],
        "clip_scale_err": _rel(got["scale"], min(
            1.0, o["clip_global_norm"] / got["gnorm"])),
        "by_param": by_param,
        "limits": {"routing_margin": ROUTING_MARGIN,
                   "routing_flip_max": ROUTING_FLIP_MAX,
                   "logits": LOGITS_TOL, "logits_rms": LOGITS_RMS_TOL,
                   "loss": LOSS_TOL, "timed_twin": TIMED_TWIN_TOL,
                   "timed_twin_last": TIMED_TWIN_LAST_TOL,
                   "grad_by_kind": GRAD_LIMITS,
                   "grad_else": GRAD_LIMITS_ELSE,
                   "global_grad_norm": GLOBAL_NORM_TOL,
                   "update": UPDATE_TOL,
                   "update_gate_scalars": UPDATE_TOL_GATE_SCALARS,
                   "clip_scale": CLIP_SCALE_TOL,
                   "delta": DELTA_TOL, "delta_rms": DELTA_RMS_TOL,
                   "delta_op_rms": DELTA_OP_RMS_TOL,
                   "delta_state_rms": DELTA_STATE_RMS_TOL,
                   "delta_float32_op_rms": DELTA_F32_OP_RMS_TOL,
                   "delta_float32_state_rms": DELTA_F32_STATE_RMS_TOL,
                   "delta_float32_op_head_median":
                   DELTA_F32_OP_HEAD_MEDIAN_TOL,
                   "delta_float32_state_head_median":
                   DELTA_F32_STATE_HEAD_MEDIAN_TOL,
                   "delta_float32_op_head_quartile":
                   DELTA_F32_OP_HEAD_QUARTILE_TOL,
                   "delta_float32_state_head_quartile":
                   DELTA_F32_STATE_HEAD_QUARTILE_TOL,
                   "conv_op_rms": CONV_OP_RMS_TOL,
                   "attention": ATTENTION_TOL,
                   "attention_rms": ATTENTION_RMS_TOL,
                   "norm_scale": NORM_SCALE_TOL},
    }
    report["worst"] = {
        k: [f(v[k] for v in by_param.values() if v[k] is not None)
            for f in (min, max)]
        for k in ("grad_cos", "grad_norm_ratio", "update_err")}
    report["failed"] = verdict(report, timed is not None)
    report["ok"] = not report["failed"]
    # every number `verdict` read beside its limit, the failing ones
    # first: the harness prints these last, on standard error and in the
    # result's line
    report["compared"] = held.compared(numbers_held(report,
                                                    timed is not None))
    return report


def _grad_limits(key):
    return GRAD_LIMITS.get("expert" if key.startswith("expert_") else key,
                           GRAD_LIMITS_ELSE)


def numbers_held(report, timed=False):
    """{the number's name: (the reading of a `judge` report, its limit)} of
    EVERY number `verdict` reads, each entry reading `reading <= limit` (a
    cosine as 1 - cos, a norm ratio as |ratio - 1|, an exact check as a
    count against 0); a reading the report does not hold is None. `verdict`
    holds the readings THROUGH this table, the run prints it last
    (`compared`, the failing ones first) and `chipbench.limits_study` lays
    the part set again (`SET_AGAIN`) over the rows on record, so a limit
    and what it reads are spelt once (`chipbench/held.py`)."""
    routing = report["routing"] + report["routing_inference"]
    parts = report.get("delta_rule_in_float32_err_rms_by_part") or {}
    steps = report.get("timed_steps") or {}
    operators = report["operator_branch_err_max_rms"]
    by_param = report["by_param"]

    def rms(key, layers=None):
        return max(v[1] for k, v in report[key].items()
                   if layers is None or k in layers)

    def over_heads(which, statistic):
        return max(float(statistic(p[which])) for p in parts.values()) \
            if parts else None

    def quartile(values):
        return np.quantile(values, 0.75)

    found = {
        "DELTA_TOL": (max(operators[k][0] for k in DELTA_LAYERS), DELTA_TOL),
        "DELTA_RMS_TOL": (rms("operator_branch_err_max_rms", DELTA_LAYERS),
                          DELTA_RMS_TOL),
        "DELTA_OP_RMS_TOL": (rms("delta_rule_op_err_max_rms"),
                             DELTA_OP_RMS_TOL),
        "DELTA_STATE_RMS_TOL": (rms("delta_rule_final_state_err_max_rms"),
                                DELTA_STATE_RMS_TOL),
        "DELTA_F32_OP_RMS_TOL": (
            rms("delta_rule_in_float32_op_err_max_rms"),
            DELTA_F32_OP_RMS_TOL),
        "DELTA_F32_STATE_RMS_TOL": (
            rms("delta_rule_in_float32_final_state_err_max_rms"),
            DELTA_F32_STATE_RMS_TOL),
        "DELTA_F32_OP_HEAD_MEDIAN_TOL": (
            over_heads("op_by_head", np.median),
            DELTA_F32_OP_HEAD_MEDIAN_TOL),
        "DELTA_F32_STATE_HEAD_MEDIAN_TOL": (
            over_heads("state_by_head", np.median),
            DELTA_F32_STATE_HEAD_MEDIAN_TOL),
        "DELTA_F32_OP_HEAD_QUARTILE_TOL": (
            over_heads("op_by_head", quartile),
            DELTA_F32_OP_HEAD_QUARTILE_TOL),
        "DELTA_F32_STATE_HEAD_QUARTILE_TOL": (
            over_heads("state_by_head", quartile),
            DELTA_F32_STATE_HEAD_QUARTILE_TOL),
        "CONV_OP_RMS_TOL": (rms("conv_op_err_max_rms"), CONV_OP_RMS_TOL),
        "ATTENTION_TOL": (operators["attention"][0], ATTENTION_TOL),
        "ATTENTION_RMS_TOL": (operators["attention"][1], ATTENTION_RMS_TOL),
        **{f"NORM_SCALE_TOL[{k}]": (scale, NORM_SCALE_TOL[k]) for k, (_, scale)
           in report["operator_input_err_rms_rowscale"].items()},
        "ROUTING_FLIP_MAX": (max(r["flipped_share"] for r in routing),
                             ROUTING_FLIP_MAX),
        "ROUTING_MARGIN": (max(r["worst_gap_in_spreads"] for r in routing),
                           ROUTING_MARGIN),
        "ROUTING layers judged on no token": (
            sum(not r["tokens"] for r in routing), 0),
        "LOGITS_TOL": (report["logits_err_max"], LOGITS_TOL),
        "LOGITS_RMS_TOL": (report["logits_err_rms"], LOGITS_RMS_TOL),
        "LOSS_TOL": (report["train_loss_err"], LOSS_TOL),
        "GLOBAL_NORM_TOL": (report["global_grad_norm_err"], GLOBAL_NORM_TOL),
        "CLIP_SCALE_TOL": (report["clip_scale_err"], CLIP_SCALE_TOL),
    }
    found.update(held.gradients(by_param, _grad_limits))
    for name, gate_scalars, limit in (
            ("UPDATE_TOL", False, UPDATE_TOL),
            ("UPDATE_TOL_GATE_SCALARS", True, UPDATE_TOL_GATE_SCALARS)):
        errs = [v["update_err"] for k, v in by_param.items()
                if k.startswith(("A_log", "dt_bias")) == gate_scalars]
        if errs:
            found[name] = (max(errs), limit)
    found.update(held.product_rows(report))
    if timed:
        # steps 0 and 1 against the reference; step 0 (the same weights,
        # the same rows) and the chunk's LAST step against the second
        # build: step 1 is reported and not judged (`TIMED_TWIN_LAST_TOL`)
        err = steps.get("err") or ()
        found["TIMED LOSS_TOL"] = (max(err) if len(err) == 2 else None,
                                   LOSS_TOL)
        found["TIMED_TWIN_TOL"] = (
            (steps.get("err_second_build") or [None])[0], TIMED_TWIN_TOL)
        found["TIMED_TWIN_LAST_TOL"] = (steps.get("err_second_build_last"),
                                        TIMED_TWIN_LAST_TOL)
    return found


# which numbers each check holds, by the prefix of their names
CHECKS = {"delta": ("DELTA_TOL", "DELTA_RMS_TOL"),
          "delta_op": ("DELTA_OP_RMS_TOL",),
          "delta_state": ("DELTA_STATE_RMS_TOL",),
          "delta_precision": ("DELTA_F32_",), "conv_op": ("CONV_OP_RMS_TOL",),
          "attention": ("ATTENTION_",), "norms": ("NORM_SCALE_TOL",),
          "routing": ("ROUTING",), "logits": ("LOGITS",),
          "loss": ("LOSS_TOL",), "global_grad_norm": ("GLOBAL_NORM_TOL",),
          "clip_scale": ("CLIP_SCALE_TOL",), "gradients": ("GRAD[",),
          "update": ("UPDATE_TOL",), "product_rows": ("product_rows",)}
TIMED_CHECKS = {"timed_steps": ("TIMED LOSS_TOL",),
                "timed_steps_second_build": ("TIMED_TWIN",)}
# the limits set again from rows on record (PR 48; PR 56: what the routed
# reference let tighten, and what its census left under M), which
# `limits_study table` and its test hold to M
SET_AGAIN = ("ROUTING_FLIP_MAX", "ROUTING_MARGIN", "LOGITS_TOL",
             "DELTA_RMS_TOL", "DELTA_F32_OP_RMS_TOL",
             "DELTA_F32_STATE_RMS_TOL", "DELTA_F32_OP_HEAD_MEDIAN_TOL",
             "DELTA_F32_STATE_HEAD_MEDIAN_TOL",
             "DELTA_F32_OP_HEAD_QUARTILE_TOL",
             "DELTA_F32_STATE_HEAD_QUARTILE_TOL", "TIMED_TWIN_TOL",
             "TIMED_TWIN_LAST_TOL") + tuple(
                 f"GRAD[{k}] {what}" for k in (
                     "router", "router_attn", "expert_gate", "expert_up",
                     "expert_down") for what in ("1 - cos", "ratio"))
# the numbers that read otherwise once the reference is routed as the
# system routed: a row read against the plain reference says nothing of
# their limits (`limits_study`)
FOLLOWS_ROUTING = ("ROUTING", "LOGITS", "LOSS_TOL", "GLOBAL_NORM_TOL",
                   "GRAD[", "NORM_SCALE_TOL", "TIMED LOSS_TOL")


def numbers_set_again(report):
    return held.set_again(numbers_held(report, timed=True), SET_AGAIN)


def verdict(report, timed=False, without=()):
    """Which checks the numbers of a `judge` report fail, by name: the
    report's own numbers against THIS module's limits (a study's saved
    reports can be judged again after a limit was set from them:
    `chipbench/tests/test_limits_study.py` does); `without`: name prefixes
    of numbers a record does not hold."""
    return held.failed_checks(
        numbers_held(report, timed),
        dict(CHECKS, **(TIMED_CHECKS if timed else {})), without)


def against_reference(fluid, cfg, builder, place, seed, tokens, labels,
                      timed=None):
    """`tokens`, `labels`: int32 [2 x rows, S], the rows of the cell's own
    steps 0 and 1; `timed`: as `judge` takes it. Returns a report with
    `ok` and every number. The caller has freed the timed program's scope;
    the system's scope here is freed before the reference runs."""
    import jax

    from chipbench.harness import memory_peak

    t0 = time.perf_counter()
    rows = int(cfg["reference"]["rows"])
    # the second build follows the timed scan through its first chunk
    # where the kind hands that over, else through step 1
    t_all, l_all = (timed or {}).get("chunk_rows", (tokens, labels))
    got = system_side(fluid, cfg, builder, place, seed, tokens[:rows],
                      labels[:rows], then=(t_all[rows:], l_all[rows:]))
    gc.collect()
    ref = reference_of(cfg, builder, got, tokens, labels)
    report = judge(cfg, builder, got, ref, timed)
    report["device_peak_bytes"] = int(memory_peak(jax.local_devices()))
    report["seconds"] = time.perf_counter() - t0
    return report
