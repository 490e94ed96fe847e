"""The `train_tokens_window_share` kind end to end on the CPU rehearsal
path at a tiny override of the `laguna_xs_2` configuration (hidden 64, 16
experts of which 4 held, 3 / 4 query heads by layer type on 1 key/value
head, a window of 8 in rows of 32, layers [full + dense, window, window,
full]): counts and control flow only (metrics present, no compile in the
window, every token routed, the products took the held rows, the reference
comparison with its first-hand attention branches wired through); no
number here is a timing. And the cell's files: the costs' counts (a band
is counted as a band), the readers on a made reduction, BENCHMARK.json's
entries."""

import io
import json
import os

import pytest

from chipbench import costs, costs_window_share, harness

HERE = os.path.dirname(__file__)
CELL = "laguna_xs_2_train_packed8k"
FULL, WINDOW = "full_attention", "sliding_attention"
TINY = {"config": {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_hidden_layers": 4,
    "head_dim": 16, "num_attention_heads": 3,
    "num_attention_heads_per_layer": [3, 4, 4, 3],
    "num_key_value_heads": 1, "num_experts": 4, "num_experts_per_tok": 2,
    "sliding_window": 8, "vocab_size": 256, "sequence_length": 32,
    "eos_token_id": 255,
    "layer_types": [FULL, WINDOW, WINDOW, FULL],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 16},
    "deployment": {"num_experts": 16, "first_expert": 8},
    # float32: the comparison's limits are set at the published widths,
    # and 32 tokens of width 64 do not average bf16 rounding as 8192 of
    # width 2048 do
    "amp": None},
    "traffic": {"steps_per_chunk": 2, "distinct_chunks": 3,
                "trace_chunks": 2, "doc_len_median": 10,
                "doc_len_min": 2, "doc_len_max": 32}}
SWA_METRICS = {
    "host_dispatch_ms", "device_idle_share", "peak_hbm_gb", "head_share",
    "optimizer_share", "expert_load_max_over_mean", "model_flops_util",
    "window_attention_roofline", "full_attention_roofline",
    "attention_share", "window_blocks_visited_share",
    "grouped_matmul_roofline", "expert_other_share", "held_rows_share"}
SAMPLED = {"head", "embedding", "w_q_full", "w_k_full", "w_q_window",
           "w_k_window", "w_v", "w_g", "w_o", "router", "expert_gate",
           "expert_up", "expert_down", "shared_gate", "shared_up",
           "shared_down", "norm_scale"}


def _run(trace):
    out = io.StringIO()
    line = harness.run_cell(CELL, seed=2 ** 31 + 29, seconds=2.0,
                            trace=trace, rehearsal=True, override=TINY,
                            files=harness.Files(), out=out)
    return line, [json.loads(v) for v in out.getvalue().splitlines()]


def test_window_share_cell_untraced():
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
    lo, hi = detail["held_rows_share"]
    assert 0.0 <= lo <= hi <= 1.0
    assert len(detail["held_rows_share_by_layer"]) == 3
    # two window layers of 4 heads, forward + dK/dV + dQ; a row of 32 is
    # one block, so the band's grid is the triangle's
    assert detail["window_blocks"] == {"visited": 24, "full_causal": 24}
    # float32 on the CPU: the system routes as the reference does, and
    # its attention branches are the reference's on the same input
    assert len(ref["routing"]) == len(ref["routing_inference"]) == 3
    assert all(r["flipped"] == 0 and r["sets_of_k"] for r in ref["routing"])
    assert ref["tokens_routed_alike_everywhere"] == 1.0
    assert set(ref["by_param"]) == SAMPLED
    assert set(ref["attention_branch_err_max_rms"]) == {FULL, WINDOW}
    assert all(err < 1e-4 for pair in
               ref["attention_branch_err_max_rms"].values() for err in pair)
    assert set(ref["attention_input_err_rms_rowscale"]) == {FULL, WINDOW}
    assert all(err < 1e-5 for pair in
               ref["attention_input_err_rms_rowscale"].values()
               for err in pair)
    # the executable the window times, its steps 0 and 1 against the
    # reference's first step and its second after its own update
    timed = ref["timed_steps"]
    assert timed["loss_timed_reference"][0][0] == detail["first_loss"]
    assert len(timed["err"]) == 2 and max(timed["err"]) < 1e-5
    assert "timed_steps" not in ref["failed"]
    assert detail["steps_run"] == 2 * detail["chunks_handed"]
    assert ref["router_bias_moved_by_the_rule"] == [True] * 3
    assert all(w == h == c for w, h, c in
               ref["product_rows_written_held_chosen"])
    # the comparison runs AFTER the window: it is no item of set-up, and
    # the peak read as the window closes is below the whole process's
    names = [n for n, _ in lines[0]["chipbench_setup"]["items"]]
    assert "reference_comparison" not in names and "program_build" in names
    assert detail["window_peak_bytes"] <= line["device"]["memory_peak_bytes"]


def test_window_share_cell_traced():
    line, _ = _run(True)
    # the scope-read metrics need a device plane, which XLA:CPU does not
    # write: their readers return None and the line leaves them out
    assert {"host_dispatch_ms", "swa.model_flops_util",
            "expert_load_max_over_mean", "swa.held_rows_share",
            "swa.window_blocks_visited_share", "setup_compile_s"} <= set(
                line["metrics"])
    assert not {"swa.window_attention_roofline", "swa.attention_share",
                "swa.full_attention_roofline", "swa.grouped_matmul_roofline",
                "swa.expert_other_share"} & set(line["metrics"])
    assert 0 <= line["metrics"]["swa.held_rows_share"]["value"] <= 100
    assert line["metrics"]["swa.window_blocks_visited_share"]["value"] == 100
    assert line["checks"]["window_compiles_zero"]
    assert line["checks"]["products_took_the_held_rows"]
    assert line["attempted"] == 2


def test_the_comparison_fails_a_band_off_by_one(monkeypatch):
    """What the first-hand attention check is for: a reference whose
    window is one position longer than the system's differs from it in
    the window layer's branch alone, by itself."""
    from chipbench import compare_lm_window_share as compare
    import paddle_tpu as fluid

    files = harness.Files()
    _, _, cfg, traffic, builder, kind = files.cell(CELL)
    cfg = dict(cfg, **TINY["config"])
    traffic = dict(traffic, **TINY["traffic"])
    tok, lab, _ = kind.token_rows(cfg, traffic, 7, 1)
    got = compare.system_side(fluid, cfg, builder, fluid.CPUPlace(), 3, tok,
                              lab)
    inputs = [u for u, _ in got["attention"]]
    right = compare.reference_branches(cfg, builder, got["w0"], tok, inputs)
    longer = compare.reference_branches(
        dict(cfg, sliding_window=9), builder, got["w0"], tok, inputs)
    (_, o_full), (_, o_win) = got["attention"]
    assert compare._branch_errors(o_win, right[1])[1] < 1e-5
    assert compare._branch_errors(o_win, longer[1])[1] > 1e-2
    assert compare._branch_errors(o_full, longer[0])[1] < 1e-5


def test_benchmark_entries_of_the_cell():
    bench = harness.Files().bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "laguna_xs_2"
    assert cell["traffic"] == "train_tokens_window_share_packed8k"
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "train_items_per_s")
    assert CELL in rate["workloads"]
    # the cell's own entries under its prefix, and the entries PR 48 folded
    # into one a quantity, which list the cell among their `workloads`
    mine = {m["name"].split(".", 1)[-1]: m for m in bench["per_layer"]
            if m["name"].startswith("swa.") or (
                "." not in m["name"] and m["moves"] == "train_items_per_s"
                and CELL in m["workloads"])}
    assert SWA_METRICS <= set(mine)
    files = harness.Files()
    for name, m in mine.items():
        assert (m["workloads"] == [CELL] if "." in m["name"]
                else CELL in m["workloads"])
        assert m["moves"] == "train_items_per_s"
        assert files.metric_reader("swa." + name) is not None
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    entry = next(c for c in bench["configs"] if c["name"] == "laguna_xs_2")
    assert entry["source"].endswith("poolside/Laguna-XS.2/blob/main/"
                                    "config.json")


def test_configuration_file_states_the_share():
    _, _, cfg, traffic, builder, _ = harness.Files().cell(CELL)
    shapes = builder.reference.param_shapes(cfg)
    count = 0
    for name, shape in shapes.items():
        n = 1
        for d in shape:
            n *= d
        count += n if builder.reference.trained(name) else 0
    assert count == cfg["parameters"] == 540637184
    dep = cfg["deployment"]
    assert dep["num_experts"] == 256 and cfg["num_experts"] == 32
    assert dep["first_expert"] + cfg["num_experts"] <= 256
    chips = dep["chips_sharing_a_layer"]
    assert [h * chips for h in cfg["num_attention_heads_per_layer"]] == \
        dep["num_attention_heads_per_layer"]
    assert cfg["num_key_value_heads"] * chips == dep["num_key_value_heads"]
    assert cfg["vocab_size"] * chips == dep["vocab_size"]
    assert 0 <= cfg["eos_token_id"] < cfg["vocab_size"]
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "num_attention_heads",
        "num_attention_heads_per_layer", "num_key_value_heads",
        "vocab_size"}
    for key in ("gating", "router", "router_bias", "optimizer", "qk_norm",
                "yarn", "window", "rotary_layout", "documents"):
        assert key in cfg["assumed"]
    assert "distorts" in cfg["reduced_why"]
    assert traffic["kind"] == "train_tokens_window_share"
    # the comparison is made on whole steps of the window's own chunk 0
    assert cfg["reference"]["rows"] == cfg["rows_per_step"]
    assert traffic["doc_len_max"] == cfg["sequence_length"] == 8192


# --------------------------------------------------------------- the costs
@pytest.mark.parametrize("seq,window", [(8192, 512), (8192, 1), (100, 8),
                                        (4096, 4095)])
def test_a_band_is_counted_as_a_band(seq, window):
    band = costs_window_share.attention_pairs(seq, window)
    assert band == seq * window - window * window // 2
    assert band <= costs_window_share.attention_pairs(seq)
    # by the definition: query i sees min(i + 1, window) keys; the closed
    # form counts half a pair on the diagonal as `costs_lm`'s seq^2 / 2 does
    exact = sum(min(i + 1, window) for i in range(seq))
    assert 0 <= exact - band <= window // 2 + 1


@pytest.mark.parametrize("window", [None, 8192, 10000])
def test_a_window_as_long_as_the_row_is_the_triangle(window):
    assert costs_window_share.attention_pairs(8192, window) \
        == 8192 * 8192 // 2


def test_costs_of_the_configuration():
    _, _, cfg, _, _, _ = harness.Files().cell(CELL)
    peaks = costs.peaks_for("TPU v5 lite")
    assert costs_window_share.layers(cfg) == [
        (FULL, 6, "dense"), (WINDOW, 8, "sparse"), (WINDOW, 8, "sparse"),
        (WINDOW, 8, "sparse"), (FULL, 6, "sparse")]
    assert costs_window_share.sparse_layers(cfg) == 4
    assert costs_window_share.grouped_kernels_per_step(cfg) == 36
    # at 8192 a full layer's head does 8192 / (2 x 512 - 32) = 8.26 x the
    # pairs of a window layer's
    full = costs_window_share.attention_flops(1, 6, 8192, 128, None, False)
    band = costs_window_share.attention_flops(1, 8, 8192, 128, 512, False)
    assert full == 6 * 4 * 128 * 8192 * 8192 // 2
    assert band == 8 * 4 * 128 * (8192 * 512 - 512 * 512 // 2)
    assert costs_window_share.attention_flops(1, 6, 8192, 128, None, True) \
        == 3 * full
    # K and V are read once a key/value head: 6 query heads + 1
    assert costs_window_share.attention_bytes(1, 6, 1, 8192, 128, True) \
        == 7 * 6 * 8192 * 128 * 2
    assert costs_window_share.attention_least_seconds_of(
        cfg, WINDOW, True, peaks) == pytest.approx(
            3 * costs_window_share.attention_least_seconds(
                cfg, WINDOW, 8, True, peaks))
    even = cfg["num_experts_per_tok"] * cfg["num_experts"] / 256
    parts = costs_window_share.forward_flops_per_token(cfg, 8192, even)
    assert parts["head"] == 2 * 2048 * 12544
    assert parts["dense_mlp"] == 3 * 2 * 2048 * 8192
    assert parts["held_experts"] == 4 * even * 3 * 2 * 2048 * 512
    assert parts["shared_expert"] == 4 * 3 * 2 * 2048 * 512
    assert parts["router"] == 4 * 2 * 2048 * 256
    assert parts["attention_window"] == 3 * band // 8192
    assert parts["attention_full"] == 2 * full // 8192
    assert costs_window_share.train_flops_per_token(cfg, 8192, even) == \
        3 * sum(parts.values())
    least = costs_window_share.expert_layer_least_seconds(cfg, 8192, True,
                                                          peaks)
    flops, nbytes = 2 * 8192 * 2048 * 512, (
        8192 * 2048 + 32 * 2048 * 512 + 8192 * 512) * 2
    assert least == pytest.approx(9 * max(
        flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]))


# rows the held experts of the 4 sparse layers took in each of 2 steps
BY_LAYER = [[8192, 96, 7000, 9012], [8100, 8300, 40, 7700]]


@pytest.mark.parametrize("kernels, found", [(72, True), (90, True),
                                            (None, False)])
def test_swa_readers_on_a_made_reduction(kernels, found):
    """The readers that count the program's kernels read nothing unless
    the window holds exactly what a step makes (36 grouped kernels: nine
    a sparse layer); the two attention rooflines read the flash kernels of
    their own name scope alone, each against its own least time."""
    files = harness.Files()
    _, _, cfg, _, _, _ = files.cell(CELL)
    peaks = costs.peaks_for("TPU v5 lite")
    red = {"busy_s": 0.4, "window_s": 0.41, "by_scope": {
        "moe/moe_ffn": 0.010, "moe/moe_ffn_grad": 0.020,
        "attn_full/mul": 0.02, "attn_window/mul": 0.03,
        "attn_full/causal_attention/flash_fwd": 0.004,
        "attn_full/causal_attention_grad/flash_dkv": 0.005,
        "attn_full/causal_attention_grad/flash_dq": 0.005,
        "attn_window/causal_attention/flash_fwd": 0.001,
        "attn_window/causal_attention_grad/flash_dkv": 0.002,
        "attn_window/causal_attention_grad/flash_dq": 0.002,
        "lm_head/mul": 0.03, "optimizer/adam": 0.06}, "events": {}}
    if kernels:
        red["by_scope"]["moe/moe_ffn/grouped/grouped_matmul"] = 0.02
        red["events"]["moe/moe_ffn/grouped/grouped_matmul"] = kernels
    obs = {"scopes": red, "steps_in_window": 2, "cfg": cfg,
           "tokens_per_step": 8192, "held_rows_by_layer": BY_LAYER,
           "held_rows_share": 0.125, "rate_items_per_s": 100000.0,
           "chips": 1, "peaks": peaks,
           "window_blocks": {"visited": 93, "full_causal": 528}}
    got = {name: files.metric_reader("swa." + name).read(obs)
           for name in SWA_METRICS - {"host_dispatch_ms", "peak_hbm_gb",
                                      "device_idle_share",
                                      "expert_load_max_over_mean"}}
    assert got["attention_share"] == pytest.approx(100 * 0.069 / 0.4)
    assert got["head_share"] == pytest.approx(100 * 0.03 / 0.4)
    assert got["optimizer_share"] == pytest.approx(100 * 0.06 / 0.4)
    assert got["held_rows_share"] == pytest.approx(12.5)
    assert got["window_blocks_visited_share"] == pytest.approx(
        100 * 93 / 528)
    assert 0 < got["model_flops_util"] < 100
    assert got["window_attention_roofline"] == pytest.approx(
        100 * 2 * costs_window_share.attention_least_seconds_of(
            cfg, WINDOW, True, peaks) / 0.005)
    assert got["full_attention_roofline"] == pytest.approx(
        100 * 2 * costs_window_share.attention_least_seconds_of(
            cfg, FULL, True, peaks) / 0.014)
    # the peak is the one read as the window closed, not the process's
    peak = files.metric_reader("swa.peak_hbm_gb")
    assert peak.read(dict(obs, window_peak_bytes=12242092544, device={
        "memory_peak_bytes": 15193183232})) == pytest.approx(12.242092544)
    assert peak.read(dict(obs, device={"memory_peak_bytes": 1})) is None
    # a program from before the counter existed reads nothing
    assert files.metric_reader("swa.window_blocks_visited_share").read(
        dict(obs, window_blocks=None)) is None
    if not found:
        assert got["grouped_matmul_roofline"] is None
        assert got["expert_other_share"] is None
        return
    least = sum(costs_window_share.expert_layer_least_seconds(
        cfg, rows, True, peaks) for step in BY_LAYER for rows in step)
    assert got["grouped_matmul_roofline"] == pytest.approx(
        100 * least / 0.02)
    assert got["expert_other_share"] == pytest.approx(100 * 0.03 / 0.05)


def test_lower_precision_study_tells_the_variants_apart(tmp_path,
                                                        monkeypatch):
    """The study's machinery at a tiny size on the CPU under bf16 AMP:
    bf16 master weights fail the update check, which the system as stated
    passes; a bf16 router is traced in bf16 and comes out as another
    number. (The limits that need the published widths to average the
    rounding out are not asserted.)"""
    from chipbench import lower_precision_lm_window_share as study

    monkeypatch.chdir(tmp_path)
    tiny = dict(TINY, config=dict(TINY["config"], amp="bfloat16"))
    study.main(["--seeds", str(2 ** 31 + 31), "--variants", "stated",
                "router", "masters", "band_off_by_one", "--override",
                json.dumps(tiny)])
    lines = {d["variant"]: d for d in map(json.loads, (
        tmp_path / "chiprun_out" / "lower_precision_lm_window_share.jsonl"
    ).read_text().splitlines())}
    assert "update" not in lines["stated"]["failed"]
    assert "update" in lines["masters"]["failed"]
    assert lines["router"]["report"]["logits_err_rms"] != \
        lines["stated"]["report"]["logits_err_rms"]
    # the planted fault: the system's band one position too long moves
    # the window layer's branch, and not the full layer's
    stated, band = (lines[v]["report"]["attention_branch_err_max_rms"]
                    for v in ("stated", "band_off_by_one"))
    assert band[WINDOW][1] > 3 * stated[WINDOW][1]
    assert band[FULL] == stated[FULL]
    # and the reference's band that fits the faulty system best is the
    # neighbour's, not the configuration's: `attention` fails by that alone
    fits = {v: lines[v]["report"]["window_branch_err_rms_by_reference_window"]
            for v in ("stated", "band_off_by_one")}
    assert min(fits["stated"], key=fits["stated"].get) == "8"
    assert min(fits["band_off_by_one"],
               key=fits["band_off_by_one"].get) == "9"
    assert "attention" in lines["band_off_by_one"]["failed"]


def test_a_timed_step_off_the_reference_fails_by_itself():
    """What ties the timed executable to the reference: its losses of
    steps 0 and 1 against the reference's first step and its second after
    its own update. A scan that carried nothing reads the second loss
    the reference computes beside it, and a loss off by 1% fails."""
    from chipbench import compare_lm_window_share as compare
    import paddle_tpu as fluid

    _, _, cfg, traffic, builder, kind = harness.Files().cell(CELL)
    cfg = dict(cfg, **TINY["config"])
    # a rate at which one step moves the loss
    cfg["optimizer"] = dict(cfg["optimizer"], learning_rate=1e-3)
    traffic = dict(traffic, **TINY["traffic"])
    tok, lab, _ = kind.token_rows(cfg, traffic, 7, 2)
    got = compare.system_side(fluid, cfg, builder, fluid.CPUPlace(), 3,
                              tok[:1], lab[:1])
    ref = compare.reference_side(cfg, builder, got["w0"], tok, lab,
                                 [u for u, _ in got["attention"]])
    after, unmoved = ref["second_step"]
    assert abs(after - unmoved) > 1e-4 * abs(after)
    good = compare.judge(cfg, builder, got, ref,
                         {"losses": [ref["loss"], after]})
    assert "timed_steps" not in good["failed"]
    assert good["timed_steps"]["err_had_nothing_carried"] > 1e-4
    for losses in ([ref["loss"], after * 1.01], [ref["loss"] * 1.01, after],
                   [ref["loss"], float("nan")]):
        assert "timed_steps" in compare.judge(
            cfg, builder, got, ref, {"losses": losses})["failed"]
    assert "timed_steps" not in compare.judge(cfg, builder, got,
                                              ref)["limits"]


def test_the_bias_rule_replayed():
    import numpy as np

    from chipbench.kinds import train_tokens_window_share as kind

    loads = np.array([[4, 0, 2, 2], [1, 1, 1, 5], [2, 2, 2, 2]])
    got = kind._bias_by_the_rule(np.zeros(4, np.float32), loads, 0.01)
    step = np.float32(0.01)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(
        got, [(-step + step), (step + step), step, -step])
