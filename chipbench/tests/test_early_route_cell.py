"""The `train_tokens_early_route_share` kind end to end on the CPU rehearsal
path at a tiny override of the `smallthinker_21b_a3b` configuration (hidden
64, 16 experts of which 4 held, top-3, 7 query heads on 1 key/value head,
a window of 8 in rows of 32, layers [full without positions, window,
window, window]): counts and control flow only (metrics present, no
compile in the window, every token routed, the products took the held
rows, the comparison with layer 0's choices and the first-hand attention
branches wired through); no number here is a timing. And the cell's files:
found by name, the costs' counts, the readers on a made reduction,
BENCHMARK.json's entries."""

import io
import json
import os

import pytest

from chipbench import costs, costs_early_route_share, harness

CELL = "smallthinker_21b_a3b_train_packed8k"
TINY = {"config": {
    "hidden_size": 64, "moe_ffn_hidden_size": 32, "num_hidden_layers": 4,
    "head_dim": 16, "num_attention_heads": 7, "num_key_value_heads": 1,
    "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 3,
    "sliding_window_size": 8, "vocab_size": 256, "sequence_length": 32,
    "eos_token_id": 255,
    "deployment": {"moe_num_primary_experts": 16, "first_expert": 4},
    # float32: the comparison's limits are set at the published widths,
    # and 32 tokens of width 64 do not average bf16 rounding as 8192 of
    # width 2560 do
    "amp": None},
    "traffic": {"steps_per_chunk": 2, "distinct_chunks": 3,
                "warmup_chunks": 2, "trace_chunks": 2,
                "doc_len_median": 10, "doc_len_min": 2, "doc_len_max": 32}}
EARLY_METRICS = {
    "host_dispatch_ms", "device_idle_share", "head_share", "optimizer_share",
    "expert_load_max_over_mean", "expert_move_share", "expert_route_share",
    "row_bound_hit_share", "unscoped_share", "peak_hbm_gb",
    "model_flops_util", "attention_share", "full_attention_roofline",
    "window_attention_roofline", "window_blocks_visited_share",
    "grouped_matmul_roofline", "expert_other_share", "expert_cast_share",
    "held_rows_share"}
SAMPLED = {"head", "embedding", "w_q_full", "w_k_full", "w_q_window",
           "w_k_window", "w_v", "w_o", "router", "router_window",
           "expert_gate", "expert_up", "expert_down", "norm_scale"}


def _run(trace):
    out = io.StringIO()
    line = harness.run_cell(CELL, seed=2 ** 31 + 29, seconds=2.0,
                            trace=trace, rehearsal=True, override=TINY,
                            files=harness.Files(), out=out)
    return line, [json.loads(v) for v in out.getvalue().splitlines()]


def test_the_cell_s_files_are_found_by_name():
    files = harness.Files()
    bench, cell, cfg, traffic, builder, kind = files.cell(CELL)
    assert cfg["name"] == cell["config"] == "smallthinker_21b_a3b"
    assert traffic["kind"] == "train_tokens_early_route_share"
    assert kind.__file__.endswith("train_tokens_early_route_share.py")
    assert builder.__file__.endswith("smallthinker_21b_a3b.py")
    assert builder.reference.__name__.endswith("smallthinker_21b_a3b")
    assert os.path.exists(os.path.join(files.root, cfg["reference"]["file"]))
    for name in EARLY_METRICS:
        assert files.metric_reader("early." + name) is not None


def test_early_route_cell_untraced():
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
    # three window layers of 7 heads, forward + dK/dV + dQ; a row of 32 is
    # one block, so the band's grid is the triangle's
    assert detail["window_blocks"] == {"visited": 63, "full_causal": 63}
    # float32 on the CPU: the system routes as the reference does
    assert len(ref["routing"]) == len(ref["routing_inference"]) == 4
    assert ref["layer_0_choices_same_share_train_inference"] == [1.0, 1.0]
    assert all(r["flipped_share"] == 0 for r in ref["routing"])
    assert ref["tokens_routed_alike_everywhere"] == 1.0
    assert set(ref["by_param"]) == SAMPLED
    assert set(ref["attention_branch_err_max_rms"]) == {"full", "window"}
    assert all(err < 1e-4 for pair in
               ref["attention_branch_err_max_rms"].values() for err in pair)
    assert all(err < 1e-5 for pair in
               ref["attention_input_err_rms_rowscale"].values()
               for err in pair)
    timed = ref["timed_steps"]
    assert timed["loss_timed_reference"][0][0] == detail["first_loss"]
    assert len(timed["err"]) == 2 and max(timed["err"]) < 1e-5
    assert detail["steps_run"] == 2 * detail["chunks_handed"]
    assert ref["router_bias_moved_by_the_rule"] == [True] * 4
    assert all(w == h == c for w, h, c in
               ref["product_rows_written_held_chosen"])
    # the embedding's gradient holds the term that travels through
    # `RouterInput`: against a reference with that path cut it is further
    # off (at std 0.02 the term is small: tests/test_smallthinker.py holds
    # it with routers drawn wide)
    term = ref["router_input_term"]
    real = term["embedding_cos_ratio_against_reference"][0]
    cut = term["against_reference_with_the_path_cut"][0]
    assert 1.0 - cut > 10 * max(1.0 - real, 1e-13)
    names = [n for n, _ in lines[0]["chipbench_setup"]["items"]]
    assert "reference_comparison" not in names and "program_build" in names
    assert detail["window_peak_bytes"] <= line["device"]["memory_peak_bytes"]


def test_early_route_cell_traced():
    line, _ = _run(True)
    # the scope-read metrics need a device plane, which XLA:CPU does not
    # write: their readers return None and the line leaves them out
    assert {"host_dispatch_ms", "early.model_flops_util",
            "expert_load_max_over_mean", "early.held_rows_share",
            "early.window_blocks_visited_share",
            "early.row_bound_hit_share", "setup_compile_s"} <= set(
                line["metrics"])
    assert not {"early.window_attention_roofline", "early.attention_share",
                "early.full_attention_roofline",
                "early.grouped_matmul_roofline",
                "early.expert_other_share"} & set(line["metrics"])
    assert 0 <= line["metrics"]["early.held_rows_share"]["value"] <= 100
    assert line["checks"]["window_compiles_zero"]
    assert line["attempted"] == 2


def test_the_comparison_fails_a_router_that_reads_the_normed_state():
    """The check this configuration is about: a system whose router reads
    what the experts read (the study's planted `router_after_attention`)
    chooses other experts than the reference in layer 0 already, and
    `early_route` fails by that."""
    from chipbench import compare_lm_early_route_share as compare
    from chipbench import lower_precision_lm_early_route_share as study
    import paddle_tpu as fluid

    _, _, cfg, traffic, builder, kind = harness.Files().cell(CELL)
    cfg = dict(cfg, **TINY["config"])
    traffic = dict(traffic, **TINY["traffic"])
    tok, lab, _ = kind.token_rows(cfg, traffic, 7, 1)
    reports = {}
    for name in ("stated", "router_after_attention"):
        got = study.run_variant(name, fluid, dict(cfg, amp="bfloat16"),
                                builder, fluid.CPUPlace(), 3, tok, lab)
        ref = compare.reference_side(
            cfg, builder, got["w0"], tok, lab,
            [u for u, _ in got["attention"]], got["ids"], got["ids_eval"])
        reports[name] = compare.judge(cfg, builder, got, ref)
    assert "early_route" not in reports["stated"]["failed"]
    assert "early_route" in reports["router_after_attention"]["failed"]
    assert min(reports["router_after_attention"][
        "layer_0_choices_same_share_train_inference"]) < 0.9


def test_benchmark_entries_of_the_cell():
    bench = harness.Files().bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "smallthinker_21b_a3b"
    assert cell["traffic"] == "train_tokens_early_route_share_packed8k"
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "train_items_per_s")
    assert CELL in rate["workloads"]
    # the cell's own entries under its prefix, and the entries PR 48 folded
    # into one a quantity, which list the cell among their `workloads`
    mine = {m["name"].split(".", 1)[-1]: m for m in bench["per_layer"]
            if m["name"].startswith("early.") or (
                "." not in m["name"] and m["moves"] == "train_items_per_s"
                and CELL in m["workloads"])}
    assert EARLY_METRICS <= set(mine)
    for m in mine.values():
        assert (m["workloads"] == [CELL] if "." in m["name"]
                else CELL in m["workloads"])
        assert m["moves"] == "train_items_per_s"
    entry = next(c for c in bench["configs"]
                 if c["name"] == "smallthinker_21b_a3b")
    assert entry["source"].endswith(
        "PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json")
    assert entry["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts",
        "num_attention_heads", "num_key_value_heads", "vocab_size"]
    assert all(len(e["why"]) <= 200 for e in (cell, entry))


def test_configuration_file_states_the_share():
    _, _, cfg, traffic, builder, _ = harness.Files().cell(CELL)
    count = 0
    for name, shape in builder.reference.param_shapes(cfg).items():
        n = 1
        for d in shape:
            n *= d
        count += n if builder.reference.trained(name) else 0
    assert count == cfg["parameters"] == 593615360
    dep = cfg["deployment"]
    chips = dep["chips_sharing_a_layer"]
    assert chips == 4 and dep["chip"] == 1
    for key in ("moe_num_primary_experts", "num_attention_heads",
                "num_key_value_heads", "vocab_size"):
        assert cfg[key] * chips == dep[key]
    assert dep["first_expert"] == dep["chip"] * cfg["moe_num_primary_experts"]
    assert dep["first_head"] == dep["chip"] * cfg["num_attention_heads"]
    assert dep["first_vocab_row"] == dep["chip"] * cfg["vocab_size"]
    assert 0 <= cfg["eos_token_id"] < cfg["vocab_size"]
    for key in ("router_input", "router", "router_balance", "optimizer",
                "qk_norm", "hidden_act", "window", "rotary_layout",
                "documents", "secondary_experts"):
        assert key in cfg["assumed"]
    assert "DEPARTURE" in cfg["assumed"]["router_balance"]
    assert "distorts" in cfg["reduced_why"]
    speeds = cfg["optimizer"]["router_bias_update_speed_by_layer"]
    assert len(speeds) == cfg["num_hidden_layers"]
    assert speeds[0] == cfg["optimizer"]["router_bias_update_speed"]
    assert cfg["reference"]["rows"] == cfg["rows_per_step"]
    assert traffic["doc_len_max"] == cfg["sequence_length"] == 8192
    assert traffic["warmup_chunks"] == 6 and traffic["steps_per_chunk"] == 10


# --------------------------------------------------------------- the costs
def test_costs_of_the_configuration():
    _, _, cfg, _, _, _ = harness.Files().cell(CELL)
    peaks = costs.peaks_for("TPU v5 lite")
    c = costs_early_route_share
    assert c.layers(cfg) == ["full", "window", "window", "window"]
    assert c.sparse_layers(cfg) == 4 and c.grouped_kernels_per_step(cfg) == 36
    pairs_full = 8192 * 8192 // 2
    pairs_band = 8192 * 4096 - 4096 * 4096 // 2
    assert pairs_band * 4 == pairs_full * 3        # 75% of the triangle
    even = 6 * 16 / 64
    parts = c.forward_flops_per_token(cfg, 8192, even)
    assert parts["head"] == 2 * 2560 * 37984
    assert parts["router"] == 4 * 2 * 2560 * 64
    assert parts["held_experts"] == 4 * even * 3 * 2 * 2560 * 768
    assert parts["projections"] == 4 * 2 * 2560 * (896 * 2 + 128 * 2)
    assert parts["attention_full"] == 7 * 4 * 128 * pairs_full // 8192
    assert parts["attention_window"] == 3 * (7 * 4 * 128 * pairs_band) // 8192
    assert c.train_flops_per_token(cfg, 8192, even) == 3 * sum(parts.values())
    # the head is about half of the step's operations (`distorts`)
    assert 0.4 < parts["head"] / sum(parts.values()) < 0.6
    assert c.attention_least_seconds_of(cfg, "window", True, peaks) == \
        pytest.approx(3 * c.attention_least_seconds(cfg, "window", True,
                                                    peaks))
    assert c.attention_least_seconds(cfg, "window", True, peaks) == \
        pytest.approx(0.75 * c.attention_least_seconds(cfg, "full", True,
                                                       peaks))
    least = c.expert_layer_least_seconds(cfg, 12288, True, peaks)
    flops, nbytes = 2 * 12288 * 2560 * 768, (
        12288 * 2560 + 16 * 2560 * 768 + 12288 * 768) * 2
    assert least == pytest.approx(9 * max(
        flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]))


# rows the held experts of the 4 layers took in each of 2 steps
BY_LAYER = [[12288, 96, 11000, 25000], [12100, 12300, 40, 13700]]


@pytest.mark.parametrize("kernels, found", [(72, True), (90, True),
                                            (None, False)])
def test_early_readers_on_a_made_reduction(kernels, found):
    """The readers that count the program's kernels read nothing unless
    the window holds exactly what a step makes (36 grouped kernels: nine a
    layer), whatever epilogue scope stands before the kernel's name; the
    two attention rooflines read the flash kernels of their own name scope
    alone, each against its own least time."""
    files = harness.Files()
    _, _, cfg, _, _, _ = files.cell(CELL)
    peaks = costs.peaks_for("TPU v5 lite")
    c = costs_early_route_share
    red = {"busy_s": 0.4, "window_s": 0.41, "by_scope": {
        "moe/moe_ffn/route": 0.004, "moe/moe_ffn/dispatch": 0.006,
        "moe/moe_ffn_grad/combine": 0.020,
        "attn_full/mul": 0.02, "attn_window/mul": 0.03,
        "attn_full/causal_attention/flash_fwd": 0.004,
        "attn_full/causal_attention_grad/flash_dkv": 0.005,
        "attn_full/causal_attention_grad/flash_dq": 0.005,
        "attn_window/causal_attention/flash_fwd": 0.010,
        "attn_window/causal_attention_grad/flash_dkv": 0.020,
        "attn_window/causal_attention_grad/flash_dq": 0.020,
        "lm_head/mul": 0.03, "optimizer/adam": 0.06},
        "events": {}, "unscoped_ops": {"copy": 0.004}}
    if kernels:
        plain = "moe/moe_ffn/grouped/grouped_matmul"
        relu = "moe/moe_ffn/grouped/relu_mul/grouped_matmul"
        red["by_scope"].update({plain: 0.012, relu: 0.008})
        red["events"].update({plain: kernels - 8, relu: 8})
    obs = {"scopes": red, "steps_in_window": 2, "cfg": cfg,
           "tokens_per_step": 8192, "held_rows_by_layer": BY_LAYER,
           "held_rows_share": 0.25, "rate_items_per_s": 60000.0,
           "chips": 1, "peaks": peaks,
           "window_blocks": {"visited": 93, "full_causal": 108}}
    got = {name: files.metric_reader("early." + name).read(obs)
           for name in EARLY_METRICS - {"host_dispatch_ms", "peak_hbm_gb",
                                        "device_idle_share",
                                        "expert_load_max_over_mean"}}
    assert got["attention_share"] == pytest.approx(100 * 0.114 / 0.4)
    assert got["head_share"] == pytest.approx(100 * 0.03 / 0.4)
    assert got["optimizer_share"] == pytest.approx(100 * 0.06 / 0.4)
    assert got["expert_route_share"] == pytest.approx(100 * 0.004 / 0.4)
    assert got["expert_move_share"] == pytest.approx(100 * 0.026 / 0.4)
    assert got["expert_cast_share"] == 0.0
    assert got["unscoped_share"] == pytest.approx(1.0)
    assert got["held_rows_share"] == pytest.approx(25.0)
    # the bound is 24576 of the 49152 choice rows: one pair of 8 overflows
    assert got["row_bound_hit_share"] == pytest.approx(100 * 7 / 8)
    assert got["window_blocks_visited_share"] == pytest.approx(
        100 * 93 / 108)
    assert 0 < got["model_flops_util"] < 100
    assert got["window_attention_roofline"] == pytest.approx(
        100 * 2 * c.attention_least_seconds_of(cfg, "window", True, peaks)
        / 0.05)
    assert got["full_attention_roofline"] == pytest.approx(
        100 * 2 * c.attention_least_seconds_of(cfg, "full", True, peaks)
        / 0.014)
    peak = files.metric_reader("early.peak_hbm_gb")
    assert peak.read(dict(obs, window_peak_bytes=13600000000)) == \
        pytest.approx(13.6)
    assert peak.read(obs) is None
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
    passes; a bf16 router is traced in bf16 and comes out as another
    number; the planted router fails `early_route`. (The limits that need
    the published widths, layer 0's flips under a bf16 router among them,
    are not asserted.)"""
    from chipbench import lower_precision_lm_early_route_share as study

    monkeypatch.chdir(tmp_path)
    tiny = dict(TINY, config=dict(TINY["config"], amp="bfloat16"))
    study.main(["--seeds", str(2 ** 31 + 31), "--variants", "stated",
                "router", "masters", "router_after_attention",
                "--override", json.dumps(tiny)])
    lines = {d["variant"]: d for d in map(json.loads, (
        tmp_path / "chiprun_out"
        / "lower_precision_lm_early_route_share.jsonl"
    ).read_text().splitlines())}
    assert "update" not in lines["stated"]["failed"]
    assert "update" in lines["masters"]["failed"]
    assert "early_route" not in lines["stated"]["failed"]
    assert "early_route" in lines["router_after_attention"]["failed"]
    assert lines["router"]["report"]["train_loss"][0] != \
        lines["stated"]["report"]["train_loss"][0]
    same = {v: min(lines[v]["report"][
        "layer_0_choices_same_share_train_inference"]) for v in lines}
    assert same["stated"] == 1.0 and same["router_after_attention"] < 0.9
