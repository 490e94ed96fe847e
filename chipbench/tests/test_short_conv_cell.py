"""The `train_tokens_short_conv_share` kind end to end on the CPU rehearsal
path at a tiny override of the `lfm2_8b_a1b` configuration (hidden 64, 8
query heads on 2 key/value heads of 8, 32 experts of which 8 held, top-4,
L = 3, layers [conv + dense, attention, conv, conv, conv], rows of 32):
counts and control flow only (metrics present, no compile in the window,
every token routed, the products took the held rows, the comparison with
the first-hand conv and attention branches and the tied table wired
through); no number here is a timing. And the cell's files: found by name,
the costs' counts, the readers on a made reduction, BENCHMARK.json's
entries.

Written in the form that survives later cells (PERF.md section 7, row 32):
the cell is looked up by its name (`CELL in rate["workloads"]`), no test
counts the benchmark's cells or configurations, and the set of a prefix's
metrics is held with `<=`, not `==`.
"""

import io
import json
import os

import pytest

from chipbench import costs, costs_short_conv_share, harness

CELL = "lfm2_8b_a1b_train_packed8k"
TINY = {"config": {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
    "num_experts": 8, "vocab_size": 256, "sequence_length": 32,
    "eos_token_id": 255,
    "deployment": {"num_experts": 32, "first_expert": 8,
                   "layers_held": [0, 2, 3, 4, 5]},
    # float32: the comparison's limits are set at the published widths,
    # and 32 tokens of width 64 do not average bf16 rounding as 8192 of
    # width 2048 do
    "amp": None},
    "traffic": {"steps_per_chunk": 2, "distinct_chunks": 3,
                "warmup_chunks": 2, "trace_chunks": 2,
                "doc_len_median": 10, "doc_len_min": 2, "doc_len_max": 32}}
SCONV_METRICS = {
    "host_dispatch_ms", "device_idle_share", "head_share", "optimizer_share",
    "expert_load_max_over_mean", "expert_move_share", "expert_route_share",
    "row_bound_hit_share", "unscoped_share", "peak_hbm_gb",
    "model_flops_util", "attention_share", "full_attention_roofline",
    "grouped_matmul_roofline", "expert_other_share", "expert_cast_share",
    "held_rows_share", "embed_grad_share", "conv_operator_share",
    "short_conv_share", "short_conv_roofline", "dense_mlp_share"}
FIRST_HAND = {"conv_dense", "attention", "conv_sparse"}


def _run(trace):
    out = io.StringIO()
    line = harness.run_cell(CELL, seed=2 ** 31 + 29, seconds=2.0,
                            trace=trace, rehearsal=True, override=TINY,
                            files=harness.Files(), out=out)
    return line, [json.loads(v) for v in out.getvalue().splitlines()]


def test_the_cell_s_files_are_found_by_name():
    files = harness.Files()
    bench, cell, cfg, traffic, builder, kind = files.cell(CELL)
    assert cfg["name"] == cell["config"] == "lfm2_8b_a1b"
    assert traffic["kind"] == "train_tokens_short_conv_share"
    assert kind.__file__.endswith("train_tokens_short_conv_share.py")
    assert builder.__file__.endswith("lfm2_8b_a1b.py")
    assert builder.reference.__name__.endswith("lfm2_8b_a1b")
    assert os.path.exists(os.path.join(files.root, cfg["reference"]["file"]))
    for name in SCONV_METRICS:
        assert files.metric_reader("sconv." + name) is not None


def test_short_conv_cell_untraced():
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
    assert len(detail["held_rows_share_by_layer"]) == 4   # sparse layers
    assert isinstance(detail["balance"], bool)
    assert detail["window_blocks"] is None                # no window layer
    # float32 on the CPU: the system routes as the reference does
    assert len(ref["routing"]) == len(ref["routing_inference"]) == 4
    assert all(r["flipped_share"] == 0 for r in ref["routing"])
    assert ref["tokens_routed_alike_everywhere"] == 1.0
    assert set(ref["operator_branch_err_max_rms"]) == FIRST_HAND
    assert all(err < 1e-4 for pair in
               ref["operator_branch_err_max_rms"].values() for err in pair)
    assert all(err < 1e-5 for pair in
               ref["operator_input_err_rms_rowscale"].values()
               for err in pair)
    fit = ref["conv_branch_err_rms_by_reference_form"]
    assert fit["stated"] < 1e-4 < min(fit["taps_reversed"],
                                      fit["row_cut_in_two"])
    tied = ref["tied_table"]
    assert tied["ok"] and tied["rows_looked_up"] + tied["rows_head_only"] \
        == 256
    assert tied["lookup_alone_is_zero_off_its_rows"]
    # a system that lost the head's term reads far from the reference
    assert tied["against_reference_without_the_head_s_term"][0] < 0.99 \
        < tied["whole"][0]
    assert {"embedding", "conv_in_dense", "conv_taps_sparse", "w_q",
            "q_scale", "k_scale", "router", "expert_down"} \
        <= set(ref["by_param"])
    timed = ref["timed_steps"]
    assert timed["loss_timed_reference"][0][0] == detail["first_loss"]
    assert len(timed["err"]) == 2 and max(timed["err"]) < 1e-5
    assert detail["steps_run"] == 2 * detail["chunks_handed"]
    assert ref["router_bias_moved_by_the_rule"] == [True] * 4
    assert all(w == h == c for w, h, c in
               ref["product_rows_written_held_chosen"])
    names = [n for n, _ in lines[0]["chipbench_setup"]["items"]]
    assert "reference_comparison" not in names and "program_build" in names
    assert detail["window_peak_bytes"] <= line["device"]["memory_peak_bytes"]


def test_short_conv_cell_traced():
    line, _ = _run(True)
    # the scope-read metrics need a device plane, which XLA:CPU does not
    # write: their readers return None and the line leaves them out
    assert {"host_dispatch_ms", "sconv.model_flops_util",
            "expert_load_max_over_mean", "sconv.held_rows_share",
            "sconv.row_bound_hit_share"} <= set(line["metrics"])
    assert not {"sconv.short_conv_roofline", "sconv.conv_operator_share",
                "sconv.full_attention_roofline", "sconv.attention_share",
                "sconv.grouped_matmul_roofline",
                "sconv.expert_other_share"} & set(line["metrics"])
    assert 0 <= line["metrics"]["sconv.held_rows_share"]["value"] <= 100
    assert line["checks"]["window_compiles_zero"]
    assert line["attempted"] == 2


def test_the_comparison_fails_a_convolution_with_its_taps_reversed():
    """The check this configuration is about: a system whose convolution
    weighs the tokens the other way round (the study's planted
    `taps_reversed`) fails `conv` by itself: its branch lies nearer the
    reference's with reversed taps than the reference's as stated."""
    from chipbench import compare_lm_short_conv_share as compare
    from chipbench import lower_precision_lm_short_conv_share as study
    import paddle_tpu as fluid

    _, _, cfg, traffic, builder, kind = harness.Files().cell(CELL)
    cfg = dict(cfg, **TINY["config"])
    traffic = dict(traffic, **TINY["traffic"])
    tok, lab, _ = kind.token_rows(cfg, traffic, 7, 1)
    reports = {}
    for name in ("stated", "taps_reversed"):
        got = study.run_variant(name, fluid, dict(cfg, amp="bfloat16"),
                                builder, fluid.CPUPlace(), 3, tok, lab)
        ref = compare.reference_side(cfg, builder, got["w0"], tok, lab,
                                     *compare.own_inputs(got))
        reports[name] = compare.judge(cfg, builder, got, ref, tokens=tok)
    assert "conv" not in reports["stated"]["failed"]
    assert "conv" in reports["taps_reversed"]["failed"]
    fit = reports["taps_reversed"]["conv_branch_err_rms_by_reference_form"]
    assert fit["taps_reversed"] < fit["stated"]


def test_the_comparison_fails_gates_and_tap_sums_in_bf16():
    """The op's precision is held by the op alone: with its gates and tap
    sums in bf16 (the study's `conv`) the op's output lies several
    roundings from the reference's gated convolution of the op's own
    input, as stated one."""
    from chipbench import compare_lm_short_conv_share as compare
    from chipbench import lower_precision_lm_short_conv_share as study
    import paddle_tpu as fluid

    _, _, cfg, traffic, builder, kind = harness.Files().cell(CELL)
    cfg = dict(cfg, **TINY["config"])
    traffic = dict(traffic, **TINY["traffic"])
    tok, lab, _ = kind.token_rows(cfg, traffic, 7, 1)
    study._wrap_kernels()
    errs = {}
    for name in ("stated", "conv"):
        got = study.run_variant(name, fluid, dict(cfg, amp="bfloat16"),
                                builder, fluid.CPUPlace(), 3, tok, lab)
        ref = compare.reference_conv_ops(cfg, builder, got["w0"], tok,
                                         compare.own_inputs(got)[1])
        errs[name] = max(compare._branch_errors(got["conv_ops"][k][1],
                                                ref[k])[1] for k in ref)
    assert errs["stated"] <= compare.CONV_OP_RMS_TOL < errs["conv"]


def cfg_source():
    return harness.Files().cell(CELL)[2]["source"]


def test_benchmark_entries_of_the_cell():
    bench = harness.Files().bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "lfm2_8b_a1b"
    assert cell["traffic"] == "train_tokens_short_conv_share_packed8k"
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "train_items_per_s")
    assert CELL in rate["workloads"]
    # the cell's own entries under its prefix, and the entries PR 48 folded
    # into one a quantity, which list the cell among their `workloads`
    mine = {m["name"].split(".", 1)[-1]: m for m in bench["per_layer"]
            if m["name"].startswith("sconv.") or (
                "." not in m["name"] and m["moves"] == "train_items_per_s"
                and CELL in m["workloads"])}
    assert SCONV_METRICS <= set(mine)
    for m in mine.values():
        assert CELL in m["workloads"] and m["moves"] == "train_items_per_s"
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2_8b_a1b")
    assert "LiquidAI/LFM2-8B-A1B/blob/main/config.json" in entry["source"]
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "num_experts", "vocab_size"]
    assert all(len(e["why"]) <= 200 for e in (cell, entry))
    assert len(entry["source"]) <= 200 and entry["source"] == cfg_source()


def test_configuration_file_states_the_share():
    _, _, cfg, traffic, builder, _ = harness.Files().cell(CELL)
    count = 0
    for name, shape in builder.reference.param_shapes(cfg).items():
        n = 1
        for d in shape:
            n *= d
        count += n if builder.reference.trained(name) else 0
    assert count == cfg["parameters"] == 507820160
    dep = cfg["deployment"]
    chips = dep["chips_sharing_a_layer"]
    assert chips == 4 and dep["chip"] == 1
    for key in ("num_experts", "vocab_size"):
        assert cfg[key] * chips == dep[key]
    assert dep["first_expert"] == dep["chip"] * cfg["num_experts"]
    assert dep["first_vocab_row"] == dep["chip"] * cfg["vocab_size"]
    assert len(dep["layers_held"]) == cfg["num_hidden_layers"]
    assert "whole" in dep["what"] and "NOT divided" in dep["what"]
    assert 0 <= cfg["eos_token_id"] < cfg["vocab_size"]
    for key in ("conv", "qk_norm", "rotary_layout", "tied_head",
                "norm_topk_eps", "final_norm", "expert_bias_rule",
                "documents", "init", "optimizer"):
        assert key in cfg["assumed"]
    assert "distorts" in cfg["reduced_why"]
    assert "arithmetic" in cfg["reduced_why"]
    assert cfg["amp"] == "bfloat16" and "ONE rounding" in cfg["amp_precision"]
    assert cfg["reference"]["rows"] == cfg["rows_per_step"]
    assert traffic["doc_len_max"] == cfg["sequence_length"] == 8192
    # the traffic file's parameters are the issue's
    assert {k: traffic[k] for k in (
        "steps_per_chunk", "distinct_chunks", "warmup_chunks",
        "trace_chunks", "doc_len_median", "doc_len_sigma", "doc_len_min",
        "doc_len_max", "zipf_exponent")} == dict(
            steps_per_chunk=10, distinct_chunks=32, warmup_chunks=6,
            trace_chunks=4, doc_len_median=600, doc_len_sigma=1.2,
            doc_len_min=16, doc_len_max=8192, zipf_exponent=1.1)
    assert cfg["optimizer"]["learning_rate"] == 1e-6
    assert cfg["optimizer"]["router_bias_update_speed"] == 0.01


# --------------------------------------------------------------- the costs
def test_costs_of_the_configuration():
    _, _, cfg, _, _, _ = harness.Files().cell(CELL)
    peaks = costs.peaks_for("TPU v5 lite")
    c = costs_short_conv_share
    assert c.layers(cfg) == ["conv", "full_attention", "conv", "conv", "conv"]
    assert c.sparse_layers(cfg) == 4 and c.grouped_kernels_per_step(cfg) == 36
    even = 4 * 8 / 32
    parts = c.forward_flops_per_token(cfg, 8192, even)
    assert parts["head"] == 2 * 2048 * 16384
    assert parts["router"] == 4 * 2 * 2048 * 32
    assert parts["held_experts"] == 4 * even * 3 * 2 * 2048 * 1792
    assert parts["dense_mlp"] == 3 * 2 * 2048 * 7168
    assert parts["conv_projections"] == 4 * 2 * 2048 * (6144 + 2048)
    assert parts["short_conv"] == 4 * 8 * 2048
    assert parts["attention_projections"] == 2 * 2048 * (2048 * 2 + 512 * 2)
    # the real head size: 64, not a lane tile's 128
    assert parts["attention"] == 32 * 4 * 64 * (8192 * 8192 // 2) // 8192
    assert c.train_flops_per_token(cfg, 8192, even) == 3 * sum(parts.values())
    # the issue's 1.31 GFLOP a token
    assert 1.28e9 < c.train_flops_per_token(cfg, 8192, even) < 1.34e9
    # the op is bound by its bytes: X in, Out out; X, d Out in, d X out
    n = 8192 * 2048 * 2
    assert c.short_conv_bytes(cfg, False) == 4 * n == 134217728
    assert c.short_conv_bytes(cfg, True) == 11 * n
    assert c.short_conv_least_seconds(cfg, True, peaks) == pytest.approx(
        11 * n / peaks["hbm_bytes_per_s"])
    assert c.short_conv_least_seconds_of(cfg, True, peaks) == pytest.approx(
        4 * c.short_conv_least_seconds(cfg, True, peaks))
    assert c.attention_least_seconds_of(cfg, True, peaks) == \
        pytest.approx(c.attention_least_seconds(cfg, True, peaks))
    least = c.expert_layer_least_seconds(cfg, 8192, True, peaks)
    flops, nbytes = 2 * 8192 * 2048 * 1792, (
        8192 * 2048 + 8 * 2048 * 1792 + 8192 * 1792) * 2
    assert least == pytest.approx(9 * max(
        flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]))


# rows the held experts of the 4 sparse layers took in each of 2 steps
BY_LAYER = [[8192, 96, 8000, 17000], [8100, 8300, 40, 9700]]


@pytest.mark.parametrize("kernels, found", [(72, True), (90, True),
                                            (None, False)])
@pytest.mark.parametrize("lowering", ["kernel", "plain"])
def test_sconv_readers_on_a_made_reduction(kernels, found, lowering):
    """The readers on a recorded `obs`: the conv operator's share is
    everything under `conv/`, the op's share and roofline read its two
    scopes whatever lowers them (a Pallas kernel's name or XLA's parts
    stand below the op's on the path), the attention roofline the flash
    kernels under `attn`; the readers that count the grouped kernels read
    nothing unless the window holds exactly what a step makes."""
    files = harness.Files()
    _, _, cfg, _, _, _ = files.cell(CELL)
    peaks = costs.peaks_for("TPU v5 lite")
    c = costs_short_conv_share
    conv = {"conv/short_conv/short_conv/short_conv_fwd": 0.002,
            "conv/short_conv/short_conv_grad/short_conv_bwd": 0.003} \
        if lowering == "kernel" else {
            "conv/short_conv/short_conv/taps": 0.0015,
            "conv/short_conv/short_conv/gate": 0.0005,
            "conv/short_conv/short_conv_grad/gate": 0.002,
            "conv/short_conv/short_conv_grad/filter_grad": 0.001}
    red = {"busy_s": 0.4, "window_s": 0.41, "by_scope": dict(conv, **{
        "conv/norm/rms_norm": 0.004, "conv/in_proj/mul": 0.03,
        "conv/out_proj/mul_grad": 0.02,
        "moe/moe_ffn/route": 0.004, "moe/moe_ffn/dispatch": 0.006,
        "moe/moe_ffn_grad/combine": 0.020,
        "attn/mul": 0.02, "attn/norm/rms_norm": 0.001,
        "attn/causal_attention/flash_fwd": 0.004,
        "attn/causal_attention_grad/flash_dkv": 0.005,
        "attn/causal_attention_grad/flash_dq": 0.005,
        "dense_mlp/mul": 0.03, "embed/lookup_table_grad/row_tile_sum": 0.002,
        "lm_head/matmul": 0.03, "optimizer/adam(conv.short_conv)": 0.06}),
        "events": {}, "unscoped_ops": {"copy": 0.004}}
    if kernels:
        plain = "moe/moe_ffn/grouped/grouped_matmul"
        silu = "moe/moe_ffn/grouped/silu_mul/grouped_matmul"
        red["by_scope"].update({plain: 0.012, silu: 0.008})
        red["events"].update({plain: kernels - 8, silu: 8})
    obs = {"scopes": red, "steps_in_window": 2, "cfg": cfg,
           "tokens_per_step": 8192, "held_rows_by_layer": BY_LAYER,
           "held_rows_share": 0.25, "rate_items_per_s": 55000.0,
           "chips": 1, "peaks": peaks}
    got = {name: files.metric_reader("sconv." + name).read(obs)
           for name in SCONV_METRICS - {"host_dispatch_ms", "peak_hbm_gb",
                                        "device_idle_share",
                                        "expert_load_max_over_mean"}}
    assert got["conv_operator_share"] == pytest.approx(100 * 0.059 / 0.4)
    assert got["short_conv_share"] == pytest.approx(100 * 0.005 / 0.4)
    assert got["short_conv_roofline"] == pytest.approx(
        100 * 2 * c.short_conv_least_seconds_of(cfg, True, peaks) / 0.005)
    assert got["dense_mlp_share"] == pytest.approx(100 * 0.03 / 0.4)
    assert got["attention_share"] == pytest.approx(100 * 0.035 / 0.4)
    assert got["full_attention_roofline"] == pytest.approx(
        100 * 2 * c.attention_least_seconds_of(cfg, True, peaks) / 0.014)
    assert got["head_share"] == pytest.approx(100 * 0.03 / 0.4)
    assert got["embed_grad_share"] == pytest.approx(100 * 0.002 / 0.4)
    # the taps' update names the op's scope, and is the optimizer's
    assert got["optimizer_share"] == pytest.approx(100 * 0.06 / 0.4)
    assert got["expert_route_share"] == pytest.approx(100 * 0.004 / 0.4)
    assert got["expert_move_share"] == pytest.approx(100 * 0.026 / 0.4)
    assert got["expert_cast_share"] == 0.0
    assert got["unscoped_share"] == pytest.approx(1.0)
    assert got["held_rows_share"] == pytest.approx(25.0)
    # the bound is 16384 of the 32768 choice rows: one pair of 8 overflows
    assert got["row_bound_hit_share"] == pytest.approx(100 * 7 / 8)
    assert 0 < got["model_flops_util"] < 100
    peak = files.metric_reader("sconv.peak_hbm_gb")
    assert peak.read(dict(obs, window_peak_bytes=12300000000)) == \
        pytest.approx(12.3)
    assert peak.read(obs) is None
    # a program without the scopes (the parent): nothing to read, no raise
    bare = dict(obs, scopes=dict(red, by_scope={"lm_head/mul": 0.03}))
    for name in ("conv_operator_share", "short_conv_share",
                 "short_conv_roofline", "dense_mlp_share", "attention_share",
                 "full_attention_roofline"):
        assert files.metric_reader("sconv." + name).read(bare) is None
        assert files.metric_reader("sconv." + name).read(
            dict(obs, scopes=None)) is None
    if not found:
        assert got["grouped_matmul_roofline"] is None
        assert got["expert_other_share"] is None
        return
    least = sum(c.expert_layer_least_seconds(cfg, rows, True, peaks)
                for step in BY_LAYER for rows in step)
    assert got["grouped_matmul_roofline"] == pytest.approx(
        100 * least / 0.02)
    assert got["expert_other_share"] == pytest.approx(100 * 0.03 / 0.05)


def test_lower_precision_study_tells_the_variants_apart(tmp_path,
                                                        monkeypatch):
    """The study's machinery at a tiny size on the CPU under bf16 AMP:
    bf16 master weights fail the update check, which the system as stated
    passes; a conv with bf16 gates and taps is traced in bf16 and comes
    out as another number; the planted taps fail `conv`. (The limits that
    need the published widths are not asserted.)"""
    from chipbench import lower_precision_lm_short_conv_share as study

    monkeypatch.chdir(tmp_path)
    tiny = dict(TINY, config=dict(TINY["config"], amp="bfloat16"))
    study.main(["--seeds", str(2 ** 31 + 31), "--variants", "stated",
                "conv", "masters", "taps_reversed",
                "--override", json.dumps(tiny)])
    lines = {d["variant"]: d for d in map(json.loads, (
        tmp_path / "chiprun_out"
        / "lower_precision_lm_short_conv_share.jsonl"
    ).read_text().splitlines())}
    assert "update" not in lines["stated"]["failed"]
    assert "update" in lines["masters"]["failed"]
    assert "conv" not in lines["stated"]["failed"]
    assert "conv" in lines["taps_reversed"]["failed"]
    assert lines["conv"]["report"]["train_loss"][0] != \
        lines["stated"]["report"]["train_loss"][0]
    err = {v: lines[v]["report"]["operator_branch_err_max_rms"][
        "conv_sparse"][1] for v in lines}
    assert err["stated"] < err["conv"] < err["taps_reversed"]
