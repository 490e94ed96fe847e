"""The `train_tokens_delta_share` kind end to end on the CPU rehearsal path
at a tiny override of the `qwen3_next_80b_a3b` configuration (hidden 64, 2
key heads serving 4 value heads of 16 in chunks of 8, 8 query heads on 1
key/value head of 16, 32 experts of which 8 held, top-10, rows of 32):
counts and control flow only (metrics present, no compile in the window,
every token routed, the products took the held rows, the comparison with
the delta rule's op, the convolution's op and the three branches
first-hand wired through); no number here is a timing. And the cell's
files: found by name, the costs' counts against hand counts, the readers on
a made reduction, BENCHMARK.json's entries, a tree without the model
`Refused`.

Written in the form that survives later cells: the cell is looked up by its
name, no test counts the benchmark's cells or configurations.
"""

import io
import json
import os
import shutil
from unittest import mock

import pytest

from chipbench import costs, costs_delta_share, harness

CELL = "qwen3_next_80b_a3b_train_packed8k"
TINY = {"config": {
    "hidden_size": 64, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_attention_heads": 8,
    "num_key_value_heads": 1, "head_dim": 16, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "num_experts": 8,
    "vocab_size": 256, "sequence_length": 32, "eos_token_id": 255,
    "deployment": {"num_experts": 32, "first_expert": 8},
    # float32: the comparison's limits are set at the published widths
    "amp": None},
    "traffic": {"steps_per_chunk": 2, "distinct_chunks": 3,
                "warmup_chunks": 2, "trace_chunks": 2,
                "doc_len_median": 10, "doc_len_min": 2, "doc_len_max": 32}}
# the eight PR 43 added under the cell's own prefix, the seven it reads
# through `sconv.` entries with prefix-named readers, and the eight that
# PR 48 folded into one entry a quantity (one reader served every prefix)
GDN_METRICS = {"delta_operator_share", "delta_rule_share",
               "delta_rule_roofline", "short_conv_roofline",
               "full_attention_roofline", "grouped_matmul_roofline",
               "expert_other_share", "model_flops_util"}
SCONV_METRICS = {
    "row_bound_hit_share", "peak_hbm_gb", "attention_share",
    "expert_cast_share", "held_rows_share", "embed_grad_share",
    "short_conv_share"}
FOLDED_METRICS = {
    "host_dispatch_ms", "device_idle_share", "head_share", "optimizer_share",
    "expert_load_max_over_mean", "expert_move_share", "expert_route_share",
    "unscoped_share"}
FIRST_HAND = {"delta_first", "delta_last", "attention"}


@pytest.fixture(scope="module", autouse=True)
def chunks_of_8():
    """The lowering's chunk is its own constant and no key of the
    configuration: at rows of 32 tokens the rehearsal shortens it."""
    from paddle_tpu.parallel import delta_rule

    with mock.patch.object(delta_rule, "CHUNK", 8):
        yield


def _run(trace):
    out = io.StringIO()
    line = harness.run_cell(CELL, seed=2 ** 31 + 29, seconds=2.0,
                            trace=trace, rehearsal=True, override=TINY,
                            files=harness.Files(), out=out)
    return line, [json.loads(v) for v in out.getvalue().splitlines()]


def test_the_cell_s_files_are_found_by_name():
    files = harness.Files()
    bench, cell, cfg, traffic, builder, kind = files.cell(CELL)
    assert cfg["name"] == cell["config"] == "qwen3_next_80b_a3b"
    assert traffic["kind"] == "train_tokens_delta_share"
    assert kind.__file__.endswith("train_tokens_delta_share.py")
    assert builder.__file__.endswith("qwen3_next_80b_a3b.py")
    assert builder.reference.__name__.endswith("qwen3_next_80b_a3b")
    assert os.path.exists(os.path.join(files.root, cfg["reference"]["file"]))
    for name in GDN_METRICS:
        assert files.metric_reader("gdn." + name).__file__.endswith(
            f"gdn.{name}.py")
    for name in SCONV_METRICS:
        assert files.metric_reader("sconv." + name) is not None
    for name in FOLDED_METRICS:
        assert files.metric_reader(name).__file__.endswith(f"{name}.py")
    # the kind imports the Laguna kind's timed loop, it does not copy it
    from chipbench.kinds import train_tokens_window_share
    assert kind.window_kind is train_tokens_window_share
    with open(kind.__file__) as f:
        assert "def _timed" not in f.read()


def test_a_tree_without_the_model_is_refused_before_the_device(tmp_path):
    """The parent: the benchmark's files laid over a checkout that has no
    `paddle_tpu/models/qwen3_next.py`. The builder raises `Refused` as the
    harness reads the cell's files."""
    root = tmp_path / "parent"
    shutil.copytree(os.path.join(harness.repo_root(), "chipbench"),
                    root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.repo_root(), "BENCHMARK.json"), root)
    os.makedirs(root / "paddle_tpu" / "models")
    builder = harness.load_module(
        str(root / "chipbench" / "configs" / "qwen3_next_80b_a3b.py"))
    assert builder is not None        # the real tree has the model
    real = harness.repo_root
    harness.repo_root = lambda: str(root)
    try:
        with pytest.raises(harness.Refused, match="qwen3_next.py"):
            harness.Files(root=str(root)).cell(CELL)
    finally:
        harness.repo_root = real


def test_delta_cell_untraced():
    line, lines = _run(False)
    assert set(line["metrics"]) == {"train_items_per_s", "setup_s"}
    assert line["checks"] == {"reference": True, "losses_finite": True,
                              "window_compiles_zero": True,
                              "every_token_routed": True,
                              "products_took_the_held_rows": True,
                              "router_bias_carried": True}
    assert line["correct"] and line["failed"] == 0
    detail, ref = lines[1]["chipbench_detail"], lines[1]["reference"]
    assert detail["distinct_chunks"] == 3 and detail["chunks_handed"] >= 4
    assert len(detail["held_rows_share_by_layer"]) == 4
    assert isinstance(detail["balance"], bool)
    # float32 on the CPU: the system routes as the reference does
    assert len(ref["routing"]) == len(ref["routing_inference"]) == 4
    assert all(r["flipped_share"] == 0 for r in ref["routing"])
    assert ref["tokens_routed_alike_everywhere"] == 1.0
    assert set(ref["operator_branch_err_max_rms"]) == FIRST_HAND
    for key in ("operator_branch_err_max_rms", "delta_rule_op_err_max_rms",
                "delta_rule_final_state_err_max_rms", "conv_op_err_max_rms"):
        assert all(err < 1e-4 for pair in ref[key].values() for err in pair)
    assert set(ref["delta_rule_op_err_max_rms"]) == {"delta_first",
                                                     "delta_last"}
    assert all(err < 1e-5 for pair in
               ref["operator_input_err_rms_rowscale"].values()
               for err in pair)
    assert {"embedding", "head", "w_qkvz", "w_ba", "conv_taps", "A_log",
            "dt_bias", "A_log_last", "w_qg", "q_scale", "k_scale", "router",
            "shared_w", "expert_down"} <= set(ref["by_param"])
    timed = ref["timed_steps"]
    assert timed["loss_timed_reference"][0][0] == detail["first_loss"]
    assert len(timed["err"]) == 2 and max(timed["err"]) < 1e-5
    # the second build's own two steps: the same program, the same rows
    assert timed["loss_second_build"][0] == ref["train_loss"][0]
    assert len(timed["err_second_build"]) == 2
    assert max(timed["err_second_build"]) < 1e-5
    # the second build followed the scan's first chunk to its last step
    # (K = 2 here, 10 in the cell), and the inference program's own
    # cross-entropy at the weights as drawn stands by the step's loss
    assert timed["last_step"] == 1
    assert timed["loss_timed"] == [detail["first_loss"],
                                   timed["loss_timed_reference"][1][0]]
    assert timed["err_second_build_last"] == timed["err_second_build"][1]
    assert timed["err_unmoved_first_against_step_0"] < 1e-5
    assert timed["err_last_had_nothing_carried"] > 0
    # a scan that never carried its state reads, at the chunk's last step,
    # what nine steps move the loss by (`limits_study.json`): inside the
    # loss's limit against the reference at step 1, outside the last
    # step's limit against the second build
    from chipbench import compare_lm_delta_share as compare

    unmoved = dict(ref, timed_steps=dict(
        timed, err=[timed["err"][0], 1.9e-4],
        err_second_build=[timed["err_second_build"][0], 1.9e-4],
        err_second_build_last=1.5e-3))
    assert compare.verdict(unmoved, True) == ["timed_steps_second_build"]
    # a report that lacks the last step is not held
    old = dict(ref, timed_steps={k: v for k, v in timed.items()
                                 if k != "err_second_build_last"})
    assert compare.verdict(old, True) == ["timed_steps_second_build"]
    assert compare.LOSS_TOL > 2.3e-4 > 1.3e-4 > compare.TIMED_TWIN_TOL
    assert detail["steps_run"] == 2 * detail["chunks_handed"]
    assert all(w == h == c for w, h, c in
               ref["product_rows_written_held_chosen"])
    names = [n for n, _ in lines[0]["chipbench_setup"]["items"]]
    assert "reference_comparison" not in names and "program_build" in names
    assert ref["failed"] == [] and ref["ok"]


def test_delta_cell_traced():
    line, _ = _run(True)
    # the scope-read metrics need a device plane, which XLA:CPU does not
    # write: their readers return None and the line leaves them out
    assert {"host_dispatch_ms", "gdn.model_flops_util",
            "expert_load_max_over_mean", "sconv.held_rows_share",
            "sconv.row_bound_hit_share"} <= set(line["metrics"])
    assert not {"gdn.delta_rule_roofline", "gdn.delta_operator_share",
                "gdn.full_attention_roofline", "gdn.short_conv_roofline",
                "gdn.grouped_matmul_roofline", "gdn.expert_other_share",
                "sconv.attention_share"} & set(line["metrics"])
    # no metric of LFM2's own (its costs read another configuration)
    assert not {"sconv.model_flops_util", "sconv.conv_operator_share",
                "sconv.dense_mlp_share"} & set(line["metrics"])
    assert 0 <= line["metrics"]["sconv.held_rows_share"]["value"] <= 100
    assert line["checks"]["window_compiles_zero"]
    assert line["attempted"] == 2


def test_benchmark_entries_of_the_cell():
    bench = harness.Files().bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "qwen3_next_80b_a3b"
    assert cell["traffic"] == "train_tokens_delta_share_packed8k"
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "train_items_per_s")
    assert CELL in rate["workloads"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in GDN_METRICS:
        m = by_name["gdn." + name]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "train_items_per_s"
    for name in SCONV_METRICS:
        assert CELL in by_name["sconv." + name]["workloads"]
    for name in FOLDED_METRICS:
        assert CELL in by_name[name]["workloads"]
    for name in ("delta_rule", "short_conv", "full_attention",
                 "grouped_matmul"):
        assert by_name[f"gdn.{name}_roofline"]["better"] == "higher"
    # the driver's contract allows 128 entries (how many are free is no
    # property of this cell: later cells append their own)
    assert len(bench["per_layer"]) <= 128
    # one entry a quantity where one reader serves all: two entries share
    # a base name and a `moves` only where one has a reader of its own
    seen = {}
    for m in bench["per_layer"]:
        base = m["name"].split(".", 1)[-1]
        seen.setdefault((base, m["moves"]), []).append(m["name"])
    files = harness.Files()
    for (base, _), names in seen.items():
        plain = [n for n in names if files.find(
            "layer_metrics", n + ".py") is None]
        # `embed_grad_share` and `row_bound_hit_share` keep their prefixed
        # entries: tests/test_embed_grad_share.py and
        # tests/test_row_bound_hit_share.py (tier-1, outside the
        # benchmark's paths) look them up by those names
        if base in ("embed_grad_share", "row_bound_hit_share"):
            continue
        assert len(plain) <= 1, (base, names)
    assert all(m.get("workloads") for m in bench["per_layer"])
    entry = next(c for c in bench["configs"]
                 if c["name"] == "qwen3_next_80b_a3b")
    _, _, cfg, _, _, _ = harness.Files().cell(CELL)
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert all(len(e["why"]) <= 200 for e in (cell, entry))


def test_configuration_file_states_the_share():
    _, _, cfg, traffic, builder, _ = harness.Files().cell(CELL)
    count = 0
    for name, shape in builder.reference.param_shapes(cfg).items():
        n = 1
        for d in shape:
            n *= d
        count += n if builder.reference.trained(name) else 0
    assert count == cfg["parameters"]
    dep = cfg["deployment"]
    chips = dep["chips_sharing_a_layer"]
    assert cfg["num_experts"] * chips == dep["num_experts"] == 512
    assert cfg["num_experts"] >= 8                    # the guide's floor
    assert dep["first_expert"] == dep["chip"] * cfg["num_experts"]
    assert cfg["vocab_size"] * dep["chips_sharing_the_vocabulary"] \
        == dep["vocab_size"] == 151936
    assert dep["first_vocab_row"] == (
        dep["chip"] % dep["chips_sharing_the_vocabulary"]) * cfg["vocab_size"]
    assert "whole" in dep["what"] and "NOT divided" in dep["what"]
    assert 0 <= cfg["eos_token_id"] < cfg["vocab_size"]
    for key in ("mtp", "balance", "expert_bias", "gate_init", "init",
                "column_order", "l2_norm", "rotary_layout", "documents",
                "delta_chunk", "optimizer"):
        assert key in cfg["assumed"]
    assert "distorts" in cfg["reduced_why"]
    assert "arithmetic" in cfg["reduced_why"]
    assert cfg["amp"] == "bfloat16" and "CARRIED STATE" in cfg["amp_precision"]
    assert cfg["reference"]["rows"] == cfg["rows_per_step"]
    assert cfg["delta_chunk"] == 64
    assert traffic["doc_len_max"] == cfg["sequence_length"] == 8192
    # the traffic file's keys and parameters are the issue's
    assert {k: traffic[k] for k in (
        "steps_per_chunk", "distinct_chunks", "warmup_chunks",
        "trace_chunks", "doc_len_median", "doc_len_sigma", "doc_len_min",
        "doc_len_max", "zipf_exponent")} == dict(
            steps_per_chunk=10, distinct_chunks=32, warmup_chunks=6,
            trace_chunks=4, doc_len_median=600, doc_len_sigma=1.2,
            doc_len_min=16, doc_len_max=8192, zipf_exponent=1.1)
    assert traffic["end_to_end"] == {"train_items_per_s": "mean_items_per_s"}
    assert traffic["item"] == "token" and traffic["input"] == "resident"
    assert cfg["optimizer"]["learning_rate"] == 1e-6
    assert cfg["optimizer"]["router_bias_update_speed"] == 0


# --------------------------------------------------------------- the costs
SMALL = dict(
    rows_per_step=2, sequence_length=100, delta_chunk=8, hidden_size=64,
    head_dim=16, num_attention_heads=8, num_key_value_heads=1,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=32, linear_conv_kernel_dim=4, num_hidden_layers=4,
    full_attention_interval=4, num_experts=8, moe_intermediate_size=32,
    shared_expert_intermediate_size=48, vocab_size=256,
    deployment=dict(num_experts=32))


def test_costs_against_hand_counts_at_a_small_size():
    c = costs_delta_share
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    assert c.layers(SMALL) == ["linear_attention"] * 3 + ["full_attention"]
    assert c.tokens(SMALL) == 200 and c.grouped_kernels_per_step(SMALL) == 36
    # a chunk of 8 tokens of one value head: k k^T and q k^T [8, 8] over 16
    # (2 x 2 x 64 x 16), the solve on [8, 16 + 32] (64 x 48), W S, Q S and
    # K^T U ([8, 16] x [16, 32], x 2, three of them), P U ([8, 8] x [8, 32])
    chunk = 2 * 2048 + 3072 + 3 * 8192 + 4096
    assert c.delta_rule_flops_a_chunk(SMALL) == chunk == 35840
    # 13 chunks a row (the last one padded), 2 rows, 4 value heads
    assert c.delta_rule_flops(SMALL, False) == 2 * 13 * 4 * chunk
    assert c.delta_rule_flops(SMALL, True) == 3 * 2 * 13 * 4 * chunk
    conv_channels = 2 * 2 * 16 + 4 * 32                       # 192
    inputs = 200 * conv_channels * 2 + 2 * 200 * 4 * 4
    out, states = 200 * 128 * 2, 26 * 4 * 16 * 32 * 4
    assert c.delta_rule_bytes(SMALL, False) == inputs + out + states
    assert c.delta_rule_bytes(SMALL, True) == 3 * inputs + 2 * out \
        + 2 * states
    assert c.delta_rule_least_seconds(SMALL, True, peaks) == pytest.approx(
        max(c.delta_rule_flops(SMALL, True) / 1e12,
            c.delta_rule_bytes(SMALL, True) / 1e11))
    assert c.delta_rule_least_seconds_of(SMALL, True, peaks) \
        == pytest.approx(3 * c.delta_rule_least_seconds(SMALL, True, peaks))
    n = 200 * conv_channels
    assert c.short_conv_bytes(SMALL, False) == 2 * n * 2
    assert c.short_conv_bytes(SMALL, True) == 5 * n * 2
    assert c.short_conv_flops(SMALL, False) == 12 * n
    even = 10 * 8 / 32
    parts = c.forward_flops_per_token(SMALL, 100, even)
    assert parts["delta_projections"] == 3 * 2 * 64 * (
        conv_channels + 128 + 8 + 128)
    assert parts["short_conv"] == 3 * 12 * conv_channels
    assert parts["delta_rule"] == 3 * 4 * chunk / 8
    assert parts["attention_projections"] == 2 * 64 * (
        2 * 128 + 2 * 16 + 128)
    assert parts["router"] == 4 * 2 * 64 * 32
    assert parts["held_experts"] == 4 * even * 3 * 2 * 64 * 32
    assert parts["shared_expert"] == 4 * (3 * 2 * 64 * 48 + 2 * 64)
    assert parts["head"] == 2 * 64 * 256
    assert c.train_flops_per_token(SMALL, 100, even) == 3 * sum(
        parts.values())


def test_costs_of_the_configuration():
    _, _, cfg, _, _, _ = harness.Files().cell(CELL)
    peaks = costs.peaks_for("TPU v5 lite")
    c = costs_delta_share
    # a chunk of 64 tokens of a head of 128 x 128: 10.5 MFLOP
    assert c.delta_rule_flops_a_chunk(cfg) == 10485760
    # 128 chunks, 32 heads, forward + backward: 129 GFLOP a layer
    assert c.delta_rule_flops(cfg, True) == 3 * 128 * 32 * 10485760
    # bound by its bytes on a v5e: 1.32 ms (1.08 GB: the inputs three
    # times, o twice, the states once each way) against 0.65 of operations
    flops_s = c.delta_rule_flops(cfg, True) / peaks["bf16_flops_per_s"]
    bytes_s = c.delta_rule_bytes(cfg, True) / peaks["hbm_bytes_per_s"]
    assert c.delta_rule_least_seconds(cfg, True, peaks) == max(flops_s,
                                                               bytes_s)
    assert 1.5 < bytes_s / flops_s < 2.5
    # the states once each way: 268 MB a layer each
    assert 128 * 32 * 128 * 128 * 4 == 268435456
    n = 8192 * 8192 * 2
    assert c.short_conv_bytes(cfg, True) == 5 * n
    assert c.short_conv_least_seconds_of(cfg, True, peaks) == pytest.approx(
        3 * 5 * n / peaks["hbm_bytes_per_s"])
    assert c.grouped_kernels_per_step(cfg) == 36
    even = 10 * cfg["num_experts"] / 512
    # 1.38 GFLOP a token: the delta layers' projections are 44% of it
    assert 1.3e9 < c.train_flops_per_token(cfg, 8192, even) < 1.45e9


# rows the held experts of the 4 layers took in each of 2 steps
BY_LAYER = [[2560, 96, 2400, 5200], [2500, 2700, 40, 3000]]


@pytest.mark.parametrize("kernels, found", [(72, True), (90, True),
                                            (73, True), (60, True),
                                            (None, False)])
def test_gdn_readers_on_a_made_reduction(kernels, found):
    """Every reader the cell reports through, on a recorded `obs`: a
    number each; the delta operator's share is everything under `delta/`,
    the op's share and roofline read its two scopes whatever lowers them,
    the convolution's likewise, the attention roofline the flash kernels
    under `attn`; the readers that divide by the grouped kernels' seconds
    read whatever their events number (72 are wanted of two steps; a step
    past the row bound still yields a number, and a trace that lost events
    has those made up at the mean of the ones it holds: PR 48) and nothing
    where the window holds none."""
    files = harness.Files()
    _, _, cfg, _, _, _ = files.cell(CELL)
    peaks = costs.peaks_for("TPU v5 lite")
    c = costs_delta_share
    red = {"busy_s": 0.5, "window_s": 0.51, "by_scope": {
        "delta/norm/rms_norm": 0.004, "delta/in_proj/mul": 0.03,
        "delta/in_proj/mul_grad": 0.05,
        "delta/short_conv/short_conv/taps": 0.002,
        "delta/short_conv/short_conv/gate": 0.001,
        "delta/short_conv/short_conv_grad/gate": 0.004,
        "delta/short_conv/short_conv_grad/filter_grad": 0.002,
        "delta/delta_rule/gated_delta_rule/parts": 0.02,
        "delta/delta_rule/gated_delta_rule/states": 0.01,
        "delta/delta_rule/gated_delta_rule_grad/parts": 0.05,
        "delta/delta_rule/gated_delta_rule_grad/states": 0.02,
        "delta/gated_norm/rms_norm": 0.005, "delta/out_proj/mul": 0.02,
        "moe/moe_ffn/route": 0.004, "moe/moe_ffn/dispatch": 0.006,
        "moe/moe_ffn_grad/combine": 0.020, "moe/shared/mul": 0.01,
        "attn/mul": 0.02, "attn/norm/rms_norm": 0.001,
        "attn/gate/sigmoid": 0.001,
        "attn/causal_attention/flash_fwd": 0.004,
        "attn/causal_attention_grad/flash_dkv": 0.005,
        "attn/causal_attention_grad/flash_dq": 0.005,
        "embed/lookup_table_grad/row_tile_sum": 0.002,
        "lm_head/mul": 0.03,
        "optimizer/adam(delta.delta_rule)": 0.06},
        "events": {}, "unscoped_ops": {"copy": 0.005}}
    if kernels:
        plain = "moe/moe_ffn/grouped/grouped_matmul"
        silu = "moe/moe_ffn/grouped/silu_mul/grouped_matmul"
        red["by_scope"].update({plain: 0.012, silu: 0.008})
        red["events"].update({plain: kernels - 8, silu: 8})
    obs = {"scopes": red, "steps_in_window": 2, "cfg": cfg,
           "tokens_per_step": 8192, "held_rows_by_layer": BY_LAYER,
           "held_rows_share": 1 / 32, "rate_items_per_s": 30000.0,
           "chips": 1, "peaks": peaks, "window_peak_bytes": 14100000000,
           "host_dispatch_s": [0.004, 0.006],
           "expert_load_max_over_mean": 1.4,
           "trace": {"busy_s": 0.5, "window_s": 0.51}}
    got = {"gdn." + n: files.metric_reader("gdn." + n).read(obs)
           for n in GDN_METRICS}
    got.update({"sconv." + n: files.metric_reader("sconv." + n).read(obs)
                for n in SCONV_METRICS})
    got.update({n: files.metric_reader(n).read(obs) for n in FOLDED_METRICS})
    noted = files.metric_reader("gdn.grouped_matmul_roofline").note(obs)
    delta = 0.004 + 0.08 + 0.009 + 0.1 + 0.005 + 0.02
    assert got["gdn.delta_operator_share"] == pytest.approx(
        100 * delta / 0.5)
    assert got["gdn.delta_rule_share"] == pytest.approx(100 * 0.1 / 0.5)
    assert got["gdn.delta_rule_roofline"] == pytest.approx(
        100 * 2 * c.delta_rule_least_seconds_of(cfg, True, peaks) / 0.1)
    assert got["sconv.short_conv_share"] == pytest.approx(100 * 0.009 / 0.5)
    assert got["gdn.short_conv_roofline"] == pytest.approx(
        100 * 2 * c.short_conv_least_seconds_of(cfg, True, peaks) / 0.009)
    assert got["sconv.attention_share"] == pytest.approx(100 * 0.036 / 0.5)
    assert got["gdn.full_attention_roofline"] == pytest.approx(
        100 * 2 * c.attention_least_seconds_of(cfg, True, peaks) / 0.014)
    assert got["head_share"] == pytest.approx(100 * 0.03 / 0.5)
    assert got["sconv.embed_grad_share"] == pytest.approx(100 * 0.002 / 0.5)
    # the two scalars' update names the op's scope, and is the optimizer's
    assert got["optimizer_share"] == pytest.approx(100 * 0.06 / 0.5)
    assert got["expert_route_share"] == pytest.approx(
        100 * 0.004 / 0.5)
    assert got["expert_move_share"] == pytest.approx(100 * 0.026 / 0.5)
    assert got["sconv.expert_cast_share"] == 0.0
    assert got["unscoped_share"] == pytest.approx(1.0)
    assert got["sconv.held_rows_share"] == pytest.approx(100 / 32)
    assert got["sconv.peak_hbm_gb"] == pytest.approx(14.1)
    assert 0 < got["gdn.model_flops_util"] < 100
    assert 0 <= got["sconv.row_bound_hit_share"] <= 100
    for name in ("host_dispatch_ms", "device_idle_share",
                 "expert_load_max_over_mean"):
        assert got[name] is not None and got[name] >= 0
    # a program without the scopes (the parent): nothing to read, no raise
    bare = dict(obs, scopes=dict(red, by_scope={"lm_head/mul": 0.03}))
    for name in ("delta_operator_share", "delta_rule_share",
                 "delta_rule_roofline", "short_conv_roofline",
                 "full_attention_roofline", "grouped_matmul_roofline",
                 "expert_other_share"):
        assert files.metric_reader("gdn." + name).read(bare) is None
        assert files.metric_reader("gdn." + name).read(
            dict(obs, scopes=None)) is None
    if not found:
        assert got["gdn.grouped_matmul_roofline"] is None
        assert got["gdn.expert_other_share"] is None
        return
    least = sum(c.expert_layer_least_seconds(cfg, rows, True, peaks)
                for step in BY_LAYER for rows in step)
    assert got["gdn.grouped_matmul_roofline"] == pytest.approx(
        100 * least / (0.02 * max(1.0, 72 / kernels)))
    assert got["gdn.expert_other_share"] == pytest.approx(100 * 0.03 / 0.05)
    assert noted == (None if kernels == 72 else {"grouped_kernel_events": {
        "got": kernels, "wanted": 72, "steps_in_window": 2}})


def test_lower_precision_study_tells_the_variants_apart(tmp_path,
                                                        monkeypatch):
    """The study's machinery at a tiny size on the CPU under bf16 AMP:
    each planted omission fails the first-hand check that is about it,
    which the system as stated passes; bf16 masters fail the update. (The
    limits that need the published widths, the carried state's and g's
    precision, are not asserted: four chunks do not tell them apart.)"""
    from chipbench import lower_precision_lm_delta_share as study

    monkeypatch.chdir(tmp_path)
    tiny = dict(TINY, config=dict(TINY["config"], amp="bfloat16"))
    study.main(["--seeds", str(2 ** 31 + 31), "--variants", "stated",
                "no_decay", "beta_one", "no_qk_norm", "taps_reversed",
                "no_output_gate", "masters", "state_bf16", "g_bf16",
                "--override", json.dumps(tiny)])
    lines = {d["variant"]: d for d in map(json.loads, (
        tmp_path / "chiprun_out" / "lower_precision_lm_delta_share.jsonl"
    ).read_text().splitlines())}
    stated = set(lines["stated"]["failed"])
    assert not stated & {"delta", "delta_op", "delta_state", "conv_op",
                         "attention", "update"}
    for name in ("no_decay", "beta_one", "no_qk_norm"):
        assert {"delta", "delta_op"} <= set(lines[name]["failed"]), name
        assert "conv_op" not in lines[name]["failed"]
    assert {"conv_op", "delta"} <= set(lines["taps_reversed"]["failed"])
    assert "delta_op" not in lines["taps_reversed"]["failed"]
    assert "attention" in lines["no_output_gate"]["failed"]
    assert "delta" not in lines["no_output_gate"]["failed"]
    assert "update" in lines["masters"]["failed"]
    # the carried state in bf16 is traced in bf16, g and beta are rounded:
    # other numbers
    for name in ("state_bf16", "g_bf16"):
        assert lines[name]["report"]["delta_rule_final_state_err_max_rms"] \
            != lines["stated"]["report"][
                "delta_rule_final_state_err_max_rms"], name


def test_the_study_reverses_the_taps_of_the_kernels_too(monkeypatch):
    """On a TPU place `short_conv` (gating "silu") takes the Pallas kernels
    of `parallel/short_conv.py`, not the plain form: `taps_reversed` has to
    hand both their taps the other way round, or it plants nothing on the
    chip. The kernels interpreted, inside and outside the planted context."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench import lower_precision_lm_delta_share as study
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.parallel import short_conv as kernels

    monkeypatch.setattr(kernels, "pallas_interpret", lambda: True)
    rng = np.random.RandomState(3)
    S, C = 256, 128
    x = jnp.asarray(rng.randn(2 * S, C), jnp.float32)
    w = jnp.asarray(rng.randn(4, C), jnp.float32)
    d_out = jnp.asarray(rng.randn(2 * S, C), jnp.float32)
    assert kernels.silu_takes(2 * S, C, S, 4, x.dtype)
    want = kernels.silu_conv_fwd(x, w[::-1], S)
    d_x, d_w = kernels.silu_conv_bwd(x, w[::-1], d_out, S)
    with study._planted("taps_reversed"):
        got = kernels.silu_conv_fwd(x, w, S)
        got_dx, got_dw = kernels.silu_conv_bwd(x, w, d_out, S)
        plain = lm_ops.silu_conv(x, w, S)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_dx), np.asarray(d_x))
    np.testing.assert_array_equal(np.asarray(got_dw), np.asarray(d_w)[::-1])
    np.testing.assert_allclose(np.asarray(plain), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(kernels.silu_conv_fwd(x, w, S) - want).max()) > 0.1


def test_a_scan_that_carries_nothing_is_not_correct():
    """The harness's look for a chip skipped (the rehearsal path), the rest
    of a run driven, with the TIMED path broken underneath: the K-step
    scan's body returns its state unchanged, so every step of a chunk
    trains from the weights the chunk began with. The losses stay finite
    and every other check of the timed steps holds; the last step of the
    scan's first chunk against the second build's own tells it (PR 48:
    `TIMED_TWIN_LAST_TOL`; at step 1 alone a sound run on the chip reads
    up to 9.1e-5 where such a scan reads from 1.1e-4), and it reads what
    the comparison says such a scan would read there
    (`err_last_had_nothing_carried`, the stand-in the limit's upper
    reading is taken from in every sound run). At the cell's rate of 1e-6
    three steps of this tiny model move the loss by less than the limit,
    so the rehearsal trains at 1e-2; the sound run at that rate is
    `correct`."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core import executor_core

    _, _, cfg, _, _, _ = harness.Files().cell(CELL)
    override = {"config": dict(TINY["config"], optimizer=dict(
        cfg["optimizer"], learning_rate=1e-2)),
        "traffic": dict(TINY["traffic"], steps_per_chunk=4)}

    def run():
        out = io.StringIO()
        line = harness.run_cell(CELL, seed=2 ** 31 + 31, seconds=2.0,
                                trace=False, rehearsal=True,
                                override=override, files=harness.Files(),
                                out=out)
        return line, json.loads(out.getvalue().splitlines()[1])["reference"]

    line, ref = run()
    assert line["correct"] and ref["timed_steps"]["last_step"] == 3
    sound = ref["timed_steps"]["err_second_build_last"]

    def scan_that_carries_nothing(step, iters):
        def multi(mut_state, const_state, stacked_feeds, rng):
            base_key, step0 = rng

            def body(st, xs):
                i, feeds = xs
                fetches, _ = step(st, const_state, feeds,
                                  jax.random.fold_in(base_key, step0 + i))
                return st, fetches

            st, fetches = jax.lax.scan(
                body, mut_state,
                (jnp.arange(iters, dtype=jnp.int32), stacked_feeds),
                length=iters)
            return fetches, st
        return multi

    with mock.patch.object(executor_core, "build_multi_step_fn",
                           scan_that_carries_nothing):
        line, ref = run()
    assert not line["correct"] and line["checks"]["reference"] is False
    assert line["checks"]["losses_finite"]
    assert "timed_steps_second_build" in ref["failed"]
    assert set(ref["failed"]) <= {"timed_steps_second_build", "timed_steps"}
    steps = ref["timed_steps"]
    assert steps["err_second_build"][0] < 1e-5       # the same weights
    assert steps["err_second_build_last"] > 10 * max(sound, 1e-6)
    assert steps["err_second_build_last"] == pytest.approx(
        steps["err_last_had_nothing_carried"], rel=1e-3)
