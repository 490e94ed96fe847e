"""The `train_tokens_share` kind end to end on the CPU rehearsal path at a
tiny override of the `xing4_0_29b_a4b` configuration (hidden 64, 8 experts
of which 2 held, 2 of 4 heads, 4 streams, 1 dense + 2 expert layers + the
module): counts and control flow only (metrics
present, no compile in the window, every token routed, the products took
the held rows, the reference comparison wired through); no number here is
a timing. And the cell's files: the costs' counts, the readers on a made
reduction, BENCHMARK.json's entries."""

import io
import json
import os

import pytest

from chipbench import costs, costs_share, harness

HERE = os.path.dirname(__file__)
CELL = "xing4_0_29b_a4b_train_packed4k"
TINY = {"config": {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "n_routed_experts": 2, "num_experts_per_tok": 2, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "vocab_size": 256, "sequence_length": 32,
    "eos_token_id": 255,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "deployment": {"n_routed_experts": 8, "first_expert": 4},
    # float32: the comparison's limits are set at the published widths,
    # and 32 tokens of width 64 do not average bf16 rounding as 4096 of
    # width 3584 do
    "amp": None},
    "traffic": {"steps_per_chunk": 2, "distinct_chunks": 3,
                "trace_chunks": 2, "doc_len_median": 10,
                "doc_len_min": 2, "doc_len_max": 32}}
SHARE_METRICS = {
    "host_dispatch_ms", "device_idle_share", "peak_hbm_gb", "head_share",
    "optimizer_share", "expert_load_max_over_mean", "model_flops_util",
    "attention_roofline", "grouped_matmul_roofline", "expert_other_share",
    "mhc_share", "latent_proj_share", "mtp_share", "held_rows_share"}


def _run(trace):
    out = io.StringIO()
    line = harness.run_cell(CELL, seed=2 ** 31 + 29, seconds=2.0,
                            trace=trace, rehearsal=True, override=TINY,
                            files=harness.Files(), out=out)
    return line, [json.loads(v) for v in out.getvalue().splitlines()]


def test_share_cell_untraced():
    line, lines = _run(False)
    assert set(line["metrics"]) == {"train_items_per_s", "setup_s"}
    assert line["checks"] == {"reference": True, "losses_finite": True,
                              "window_compiles_zero": True,
                              "every_token_routed": True,
                              "products_took_the_held_rows": True}
    assert line["correct"] and line["failed"] == 0
    detail, ref = lines[1]["chipbench_detail"], lines[1]["reference"]
    assert detail["distinct_chunks"] == 3 and detail["chunks_handed"] >= 4
    lo, hi = detail["held_rows_share"]
    assert 0.0 <= lo <= hi <= 1.0
    # float32 on the CPU: the system routes as the reference does, in
    # every expert layer and in the module
    assert len(ref["routing"]) == len(ref["routing_inference"]) == 3
    assert all(r["flipped"] == 0 and r["sets_of_k"] for r in ref["routing"])
    assert ref["tokens_routed_alike_everywhere"] == 1.0
    assert set(ref["by_param"]) == {
        "head", "embedding", "w_qa", "w_kvb", "w_o", "router",
        "expert_gate", "expert_up", "expert_down", "shared_gate",
        "shared_up", "shared_down", "phi_res", "alpha", "mtp_proj",
        "norm_scale"}
    assert ref["mtp_cross_entropy_err"] < 1e-5
    names = [n for n, _ in lines[0]["chipbench_setup"]["items"]]
    assert names.index("reference_comparison") < names.index("program_build")


def test_share_cell_traced():
    line, _ = _run(True)
    # the scope-read metrics need a device plane, which XLA:CPU does not
    # write: their readers return None and the line leaves them out
    assert {"host_dispatch_ms", "share.model_flops_util",
            "expert_load_max_over_mean", "share.held_rows_share",
            "setup_compile_s"} <= set(line["metrics"])
    assert not {"share.mhc_share", "share.grouped_matmul_roofline",
                "share.attention_roofline", "share.mtp_share"} & set(
                    line["metrics"])
    assert 0 <= line["metrics"]["share.held_rows_share"]["value"] <= 100
    assert line["checks"]["window_compiles_zero"]
    assert line["checks"]["products_took_the_held_rows"]
    assert line["attempted"] == 2


def test_benchmark_entries_of_the_cell():
    bench = harness.Files().bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "xing4_0_29b_a4b"
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "train_items_per_s")
    assert CELL in rate["workloads"]
    # the cell's own entries under its prefix, and the entries PR 48 folded
    # into one a quantity, which list the cell among their `workloads`
    mine = {m["name"].split(".", 1)[-1]: m for m in bench["per_layer"]
            if m["name"].startswith("share.") or (
                "." not in m["name"] and m["moves"] == "train_items_per_s"
                and CELL in m["workloads"])}
    assert SHARE_METRICS <= set(mine)
    files = harness.Files()
    for name, m in mine.items():
        assert (m["workloads"] == [CELL] if "." in m["name"]
                else CELL in m["workloads"])
        assert m["moves"] == "train_items_per_s"
        assert files.metric_reader("share." + name) is not None
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["workloads"]) >= 6


def test_configuration_file_states_the_share():
    _, _, cfg, traffic, builder, _ = harness.Files().cell(CELL)
    shapes = builder.reference.param_shapes(cfg)
    count = 0
    for shape in shapes.values():
        n = 1
        for d in shape:
            n *= d
        count += n
    assert count == cfg["parameters"]
    assert abs(count - 789.7e6) < 0.01 * 789.7e6
    dep = cfg["deployment"]
    assert dep["n_routed_experts"] == 64 and cfg["n_routed_experts"] == 8
    assert dep["first_expert"] + cfg["n_routed_experts"] <= 64
    assert cfg["num_attention_heads"] * dep["chips_sharing_a_layer"] == \
        dep["num_attention_heads"]
    assert cfg["vocab_size"] * dep["chips_sharing_a_layer"] == \
        dep["vocab_size"]
    assert 0 <= cfg["eos_token_id"] < cfg["vocab_size"]
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "num_attention_heads", "num_key_value_heads", "vocab_size"}
    for key in ("mixers", "mtp_loss_coef", "balance_term", "router_bias",
                "yarn", "rotary_layout", "optimizer"):
        assert key in cfg["assumed"]
    assert traffic["kind"] == "train_tokens_share"


def test_costs_of_the_share():
    _, _, cfg, _, _, _ = harness.Files().cell(CELL)
    peaks = costs.peaks_for("TPU v5 lite")
    assert costs_share.blocks(cfg) == (1, 5)
    assert costs_share.grouped_kernels_per_step(cfg) == 45
    assert costs_share.flash_kernels_per_step(cfg) == (6, 12)
    assert costs_share.head_sizes(cfg) == (192, 128)
    # forward attention of a 4096 row, 4 heads: half the square, Q K^T at
    # 192 and P V at 128
    assert costs_share.causal_attention_flops(
        1, 4, 4096, 192, 128, False) == 4 * 4096 * 4096 * (192 + 128)
    assert costs_share.causal_attention_flops(1, 4, 4096, 192, 128, True) \
        == 3 * costs_share.causal_attention_flops(1, 4, 4096, 192, 128,
                                                  False)
    even = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / 64
    parts = costs_share.forward_flops_per_token(cfg, 4096, even)
    assert parts["head"] == 2 * 3584 * 16384
    assert parts["dense_mlp"] == 3 * 2 * 3584 * 9216
    assert parts["held_experts"] == 5 * even * 3 * 2 * 3584 * 1024
    assert parts["shared_expert"] == 5 * 3 * 2 * 3584 * 1024
    assert costs_share.train_flops_per_token(cfg, 4096, even) == \
        3 * sum(parts.values())
    # 2048 rows of 3584 against 8 experts' [3584, 1024]: the weights'
    # bytes bind, not the operations
    least = costs_share.expert_layer_least_seconds(cfg, 2048, True, peaks)
    one_bytes = (2048 * 3584 + 8 * 3584 * 1024 + 2048 * 1024) * 2
    assert least == pytest.approx(9 * one_bytes / peaks["hbm_bytes_per_s"])


# rows the held experts of the 5 expert blocks took in each of 2 steps
BY_LAYER = [[2048, 96, 310, 512, 0], [1500, 2100, 40, 700, 1300]]


@pytest.mark.parametrize("by_layer", [
    None, [], BY_LAYER[:1], [step[:4] for step in BY_LAYER]],
    ids=["none", "empty", "a_step_short", "a_layer_short"])
def test_grouped_roofline_wants_every_layers_rows(by_layer):
    """The least time is summed over each expert block's own rows in each
    step: with a step or a block missing the reader reads nothing, it
    does not extrapolate from the layers it has."""
    files = harness.Files()
    _, _, cfg, _, _, _ = files.cell(CELL)
    key = "moe/moe_ffn/grouped/grouped_matmul"
    obs = {"scopes": {"busy_s": 0.4, "by_scope": {key: 0.02},
                      "events": {key: 90}},
           "steps_in_window": 2, "cfg": cfg, "tokens_per_step": 4096,
           "held_rows_by_layer": by_layer,
           "peaks": costs.peaks_for("TPU v5 lite")}
    assert files.metric_reader(
        "share.grouped_matmul_roofline").read(obs) is None
    assert files.metric_reader("share.grouped_matmul_roofline").read(
        dict(obs, held_rows_by_layer=BY_LAYER)) is not None


def test_model_flops_util_counts_the_mean_rows_of_the_layers():
    files = harness.Files()
    _, _, cfg, _, _, _ = files.cell(CELL)
    obs = {"cfg": cfg, "tokens_per_step": 4096, "rate_items_per_s": 30000.0,
           "chips": 1, "peaks": costs.peaks_for("TPU v5 lite"),
           "held_rows_by_layer": BY_LAYER}
    mean = sum(map(sum, BY_LAYER)) / 10
    want = costs_share.train_flops_per_token(cfg, 4096, mean / 4096)
    assert files.metric_reader("share.model_flops_util").read(obs) == \
        pytest.approx(100 * want * 30000.0 / obs["peaks"]["bf16_flops_per_s"])
    assert files.metric_reader("share.model_flops_util").read(
        dict(obs, held_rows_by_layer=None)) is None


@pytest.mark.parametrize("kernels, found", [(90, True), (108, True),
                                            (None, False)])
def test_share_readers_on_a_made_reduction(kernels, found):
    """The readers that count the program's kernels read nothing unless
    the window holds exactly what a step makes (45 grouped kernels: nine
    an expert block); the scope shares read their
    scopes."""
    files = harness.Files()
    _, _, cfg, _, _, _ = files.cell(CELL)
    red = {"busy_s": 0.4, "window_s": 0.41, "by_scope": {
        "moe/moe_ffn": 0.010, "moe/moe_ffn_grad": 0.020,
        "mtp/moe/moe_ffn": 0.002, "mhc/mhc_mix": 0.03,
        "mhc/mhc_mix_grad": 0.05, "mtp/mhc/mhc_update": 0.004,
        "attn/mul": 0.02, "attn/causal_attention": 0.004,
        "attn/causal_attention_grad": 0.006, "lm_head/mul": 0.03,
        "mtp/lm_head/mul": 0.03, "optimizer/adam": 0.06},
        "events": {}}
    if kernels:
        red["by_scope"]["moe/moe_ffn/grouped/grouped_matmul"] = 0.02
        red["events"]["moe/moe_ffn/grouped/grouped_matmul"] = kernels
    obs = {"scopes": red, "steps_in_window": 2, "cfg": cfg,
           "tokens_per_step": 4096, "held_rows_by_layer": BY_LAYER,
           "held_rows_share": 0.125, "rate_items_per_s": 30000.0,
           "chips": 1, "peaks": costs.peaks_for("TPU v5 lite")}
    got = {name: files.metric_reader("share." + name).read(obs)
           for name in SHARE_METRICS - {"host_dispatch_ms", "peak_hbm_gb",
                                        "device_idle_share",
                                        "expert_load_max_over_mean"}}
    assert got["mhc_share"] == pytest.approx(100 * 0.084 / 0.4)
    assert got["latent_proj_share"] == pytest.approx(100 * 0.02 / 0.4)
    assert got["mtp_share"] == pytest.approx(100 * 0.036 / 0.4)
    assert got["head_share"] == pytest.approx(100 * 0.06 / 0.4)
    assert got["optimizer_share"] == pytest.approx(100 * 0.06 / 0.4)
    assert got["held_rows_share"] == pytest.approx(12.5)
    assert 0 < got["model_flops_util"] < 100
    assert 0 < got["attention_roofline"] < 100
    if not found:
        assert got["grouped_matmul_roofline"] is None
        assert got["expert_other_share"] is None
        return
    # each expert block of each step at its own rows, none extrapolated
    least = sum(costs_share.expert_layer_least_seconds(
        cfg, rows, True, obs["peaks"]) for step in BY_LAYER for rows in step)
    assert got["grouped_matmul_roofline"] == pytest.approx(
        100 * least / 0.02)
    # the kernels' key lies under the op's scope
    assert got["expert_other_share"] == pytest.approx(100 * 0.032 / 0.052)


def test_lower_precision_study_tells_the_variants_apart(tmp_path,
                                                        monkeypatch):
    """The study's machinery at a tiny size on the CPU under bf16 AMP:
    bf16 master weights fail the update check, which the system as stated
    passes; bf16 mixers are traced in bf16 and come out as another
    number. (The limits that need the published widths to average the
    rounding out are not asserted.)"""
    from chipbench import lower_precision_lm_share

    monkeypatch.chdir(tmp_path)
    tiny = dict(TINY, config=dict(TINY["config"], amp="bfloat16"))
    lower_precision_lm_share.main([
        "--seeds", str(2 ** 31 + 31), "--variants", "stated", "mixers",
        "masters", "--override", json.dumps(tiny)])
    lines = {d["variant"]: d for d in map(json.loads, (
        tmp_path / "chiprun_out" / "lower_precision_lm_share.jsonl"
    ).read_text().splitlines())}
    assert "update" not in lines["stated"]["failed"]
    assert "update" in lines["masters"]["failed"]
    assert lines["mixers"]["report"]["logits_err_rms"] != \
        lines["stated"]["report"]["logits_err_rms"]


def test_reference_faults_of_the_mixers_backward_read_in_the_pooled_numbers(
        tmp_path, monkeypatch):
    """The study's second mode at a tiny size on the CPU: a fault of the
    mixers' hand-written backward planted in the reference put in the
    program's place, the weights the system's own draw. The values stay
    (the loss is asserted equal inside); d HRes with its stream axes
    exchanged turns the pooled `phi_res` and d HPost left out the pooled
    `alpha`, each beyond `POOLED_LIMITS`, and neither moves any other
    sampled parameter's gradient; the coefficients' path left out of d x
    reads under every limit (what `limits_study.json` holds of the chip
    at the cell's size: `compare_lm_share`'s comment)."""
    from chipbench import compare_lm_share, lower_precision_lm_share

    monkeypatch.chdir(tmp_path)
    tiny = dict(TINY, config=dict(TINY["config"], amp="bfloat16"))
    lower_precision_lm_share.main([
        "--seeds", str(2 ** 31 + 31), "--reference-faults",
        "--override", json.dumps(tiny)])
    lines = {d["variant"]: d for d in map(json.loads, (
        tmp_path / "chiprun_out" / "lower_precision_lm_share.jsonl"
    ).read_text().splitlines())}
    assert set(lines) == set(lower_precision_lm_share.REFERENCE_FAULTS)
    held = {k: compare_lm_share.pooled_held(d["report"])
            for k, d in lines.items()}
    assert held["res_grad_transposed"]["phi_res"] is False
    assert held["post_grad_dropped"]["alpha"] is False
    assert held["post_grad_dropped"]["phi_res"] is True
    assert lines["coefficient_path_dropped"]["ok"] is True
    for name in ("res_grad_transposed", "post_grad_dropped"):
        assert lines[name]["failed"] == ["gradients"]
        others = {k: v for k, v in lines[name]["report"]["by_param"].items()
                  if k not in ("phi_res", "alpha")}
        assert all(compare_lm_share._grad_held(k, v)
                   for k, v in others.items())
