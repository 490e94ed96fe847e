"""The `train_tokens_ssd_share` kind end to end on the CPU rehearsal path at
a tiny override of the `nemotron_3_nano_30b_a3b` configuration (hidden 64,
8 Mamba heads of 8 in 2 groups with a state of 16 in chunks of 8, 8 query
heads on 1 key/value head of 16, 32 experts of which 8 held, top-6, rows of
32): counts and control flow only (metrics present, no compile in the
window, every token routed, the products took the held rows, the bias rule
replayed, the comparison with the scan's op, the convolution's op and the
four branches first-hand wired through); no number here is a timing. And
the cell's files: found by name, the costs' counts against hand counts,
every `ssd.` reader on a made reduction, BENCHMARK.json's entries, `source`
the catalog's, a tree without the model `Refused`, `check_line` on the
lines the cell printed on the chip.

Written in the form that survives later cells: the cell is looked up by its
name, no test counts the benchmark's cells or configurations.
"""

import io
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import check_line, costs, costs_ssd_share, harness

CELL = "nemotron_3_nano_30b_a3b_train_packed4k"
CONFIG = "nemotron_3_nano_30b_a3b"
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
          "/blob/main/config.json")
HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "nemotron_lines.jsonl")
TINY = {"config": {
    "hidden_size": 64, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 64, "num_attention_heads": 8,
    "num_key_value_heads": 1, "head_dim": 16, "mamba_num_heads": 8,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "n_routed_experts": 8, "vocab_size": 256, "sequence_length": 32,
    "chunk_size": 8, "eos_token_id": 255,
    "deployment": {"n_routed_experts": 32, "first_expert": 8,
                   "num_hidden_layers": 52},
    # float32: the comparison's limits are set at the published widths;
    # a recipe's rate: at 1e-6 a step of this tiny model is below an ulp
    "amp": None,
    "optimizer": {"type": "adamw", "learning_rate": 3e-4, "beta1": 0.9,
                  "beta2": 0.95, "epsilon": 1e-8, "weight_decay": 0.1,
                  "clip_global_norm": 1.0,
                  "router_bias_update_speed": 0.01}},
    "traffic": {"steps_per_chunk": 2, "distinct_chunks": 3,
                "warmup_chunks": 2, "trace_chunks": 2,
                "doc_len_median": 10, "doc_len_min": 2, "doc_len_max": 32}}
SSD_METRICS = {
    "mixer_share", "scan_share", "scan_roofline", "conv_share",
    "gated_norm_share", "attention_share", "attention_roofline",
    "shared_expert_share", "grouped_matmul_roofline", "expert_cast_share",
    "held_rows_share", "model_flops_util", "peak_hbm_gb"}
FOLDED_METRICS = {
    "host_dispatch_ms", "device_idle_share", "head_share", "optimizer_share",
    "expert_load_max_over_mean", "unscoped_share", "expert_move_share",
    "expert_route_share", "setup_compile_s", "setup_trace_s",
    "setup_lower_s", "setup_build_self_s", "setup_builds"}
FIRST_HAND = {"mamba_first", "mamba_last", "attention", "experts"}


def _run(trace):
    out = io.StringIO()
    line = harness.run_cell(CELL, seed=2 ** 31 + 29, seconds=2.0,
                            trace=trace, rehearsal=True, override=TINY,
                            files=harness.Files(), out=out)
    return line, [json.loads(v) for v in out.getvalue().splitlines()]


def test_the_cell_s_files_are_found_by_name():
    files = harness.Files()
    bench, cell, cfg, traffic, builder, kind = files.cell(CELL)
    assert cfg["name"] == cell["config"] == CONFIG
    assert traffic["kind"] == "train_tokens_ssd_share"
    assert kind.__file__.endswith("train_tokens_ssd_share.py")
    assert builder.__file__.endswith(CONFIG + ".py")
    assert builder.reference.__name__.endswith(CONFIG)
    assert os.path.exists(os.path.join(files.root, cfg["reference"]["file"]))
    for name in SSD_METRICS:
        assert files.metric_reader("ssd." + name).__file__.endswith(
            f"ssd.{name}.py")
    for name in FOLDED_METRICS:
        assert files.metric_reader(name).__file__.endswith(f"{name}.py")
    # the kind imports the Laguna kind's timed loop, it does not copy it
    from chipbench.kinds import train_tokens_window_share
    assert kind.window_kind is train_tokens_window_share
    with open(kind.__file__) as f:
        assert "def _timed" not in f.read()
    # the traffic is the Xing cell's, so the two 4k share cells read
    # against each other
    xing = harness.load_json(files.find(
        "traffic", "train_tokens_share_packed4k.json"))
    for key in ("steps_per_chunk", "distinct_chunks", "trace_chunks",
                "doc_len_median", "doc_len_sigma", "doc_len_min",
                "doc_len_max", "zipf_exponent"):
        assert traffic[key] == xing[key], key
    assert traffic["warmup_chunks"] == 6 and cfg["sequence_length"] == 4096


def test_a_tree_without_the_model_is_refused_before_the_device(tmp_path):
    """The parent: the benchmark's files laid over a checkout that has no
    `paddle_tpu/models/nemotron_h.py`. The builder raises `Refused` as the
    harness reads the cell's files."""
    root = tmp_path / "parent"
    shutil.copytree(os.path.join(harness.repo_root(), "chipbench"),
                    root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.repo_root(), "BENCHMARK.json"), root)
    os.makedirs(root / "paddle_tpu" / "models")
    builder = harness.load_module(
        str(root / "chipbench" / "configs" / (CONFIG + ".py")))
    assert builder is not None        # the real tree has the model
    real = harness.repo_root
    harness.repo_root = lambda: str(root)
    try:
        with pytest.raises(harness.Refused, match="nemotron_h.py"):
            harness.Files(root=str(root)).cell(CELL)
    finally:
        harness.repo_root = real


def test_ssd_cell_untraced():
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
    assert ref["experts_branch_tokens_alike"] == 1.0
    assert set(ref["branch_err_max_rms"]) == FIRST_HAND
    for key in ("branch_err_max_rms", "scan_op_err_max_rms",
                "scan_final_state_err_max_rms", "conv_op_err_max_rms"):
        assert all(rms < 1e-5 for _, rms in ref[key].values()), key
    assert set(ref["scan_op_err_max_rms"]) == {"mamba_first", "mamba_last"}
    assert ref["train_loss_err"] < 1e-5 and ref["failed"] == []
    assert len(ref["timed_steps"]["err"]) == 2
    # step 1 stands behind an update at a recipe's rate, where the
    # system's epsilon placement shows (`assumed.adam_epsilon_placement`)
    assert ref["timed_steps"]["err"][0] < 1e-5
    assert ref["timed_steps"]["err"][1] < 5e-4
    assert all(w == h == c for w, h, c
               in ref["product_rows_written_held_chosen"])
    # every limit stands in the line beside what it read
    compared = line["compared"]
    from chipbench import compare_lm_ssd_share as compare
    assert compared.pop("failed") == []
    assert {k.split(":")[0] for k in compared} == set(compare.LIMITS)
    assert all(v[0] <= v[1] for v in compared.values())


def test_ssd_cell_traced():
    line, _ = _run(True)
    # the scope-read metrics need a device plane, which XLA:CPU does not
    # write: their readers return None and the line leaves them out
    assert {"host_dispatch_ms", "ssd.model_flops_util",
            "expert_load_max_over_mean", "ssd.held_rows_share",
            "ssd.peak_hbm_gb"} <= set(line["metrics"]) | set(
                line["metrics_missing"])
    assert {"ssd.model_flops_util", "ssd.held_rows_share"} \
        <= set(line["metrics"])
    assert not {"ssd.scan_roofline", "ssd.attention_roofline",
                "ssd.mixer_share", "ssd.grouped_matmul_roofline"} \
        & set(line["metrics"])
    assert 0 <= line["metrics"]["ssd.held_rows_share"]["value"] <= 100
    assert line["checks"]["window_compiles_zero"]
    assert line["attempted"] == 2
    assert check_line.problems(line, harness.Files().bench(),
                               rehearsal=True) == []


def test_benchmark_entries_of_the_cell():
    bench = harness.Files().bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "train_tokens_ssd_share_packed4k"
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "train_items_per_s")
    assert CELL in rate["workloads"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in SSD_METRICS:
        m = by_name["ssd." + name]
        assert m["workloads"] == [CELL]
        assert m["unit"] == ("GB" if name == "peak_hbm_gb" else "%")
        assert m["moves"] == "train_items_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert len([n for n in by_name if n.startswith("ssd.")]) <= 16
    for name in FOLDED_METRICS:
        assert CELL in by_name[name]["workloads"]
    for name in ("scan", "attention", "grouped_matmul"):
        assert by_name[f"ssd.{name}_roofline"]["better"] == "higher"
    assert len(bench["per_layer"]) <= 128
    assert all(m.get("workloads") for m in bench["per_layer"])
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    _, _, cfg, _, _, _ = harness.Files().cell(CELL)
    assert entry["source"] == cfg["source"] == SOURCE
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert all(len(e["why"]) <= 200 for e in (cell, entry))
    # one cell in four at most may take four chips
    fours = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(fours) <= max(1, len(bench["workloads"]) // 4)


def test_source_and_numbers_are_the_catalog_s():
    """Where the guide's catalog is at hand: `source` is its `source_url`
    letter for letter, and every number of its `config` stands in the file
    under the same key but the four the file lists as `reduced`."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(json.loads(ln) for ln in f
                   if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in ln)
    _, _, cfg, _, _, _ = harness.Files().cell(CELL)
    assert cfg["source"] == row["source_url"] == SOURCE
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"])
    for key in cfg["reduced"]:
        assert cfg["deployment"][key] == row["config"][key]
    assert set(cfg["assumed"]) >= {
        "d_inner", "positions", "gated_norm", "documents", "init",
        "rescale_prenorm_residual", "e_score_correction_bias", "optimizer"}


def test_costs_against_hand_counts_at_a_small_size():
    c = costs_ssd_share
    cfg = dict(TINY["config"], rows_per_step=2, num_hidden_layers=9,
               hybrid_override_pattern="MEMEM*EME", conv_kernel=4,
               num_experts_per_tok=6, n_shared_experts=1)
    # a chunk of 8 tokens: C B^T a group 2 x 8 x 8 x 16 x 2 groups; the
    # masked product a head 2 x 8 x 8 x 8 x 8 heads; the chunk's state and
    # the carried part 2 x 8 x 8 x 16 x 8 heads each
    assert c.scan_flops_a_chunk(cfg) == 4096 + 8192 + 16384 + 16384
    assert c.chunks(cfg) == 2 * 4
    assert c.scan_flops(cfg, False) == 8 * 45056
    assert c.scan_flops(cfg, True) == 3 * 8 * 45056
    # x [64, 64], B, C [64, 32], dt [64, 8] in, y [64, 64] out, bf16; the
    # chunk-start states [8, 8, 8, 16] float32
    inputs, out, states = 64 * (64 + 64 + 8) * 2, 64 * 64 * 2, 8 * 1024 * 4
    assert c.scan_bytes(cfg, False) == inputs + out + states
    assert c.scan_bytes(cfg, True) == 2 * (inputs + out + states) + inputs
    assert c.short_conv_bytes(cfg, False) == 2 * 64 * 128 * 2
    assert c.short_conv_bytes(cfg, True) == 5 * 64 * 128 * 2
    assert c.short_conv_flops(cfg, False) == 13 * 64 * 128
    assert c.expert_layers(cfg) == 4
    assert c.grouped_kernels_per_step(cfg) == 24
    peaks = costs.peaks_for("TPU v5 lite")
    one = c.expert_layer_least_seconds(cfg, 100, False, peaks)
    assert c.expert_layer_least_seconds(cfg, 100, True, peaks) \
        == pytest.approx(3 * one)
    parts = c.forward_flops_per_token(cfg, 32, 6 * 8 / 32)
    assert parts["mamba_projections"] == 4 * (2 * 64 * 200 + 2 * 64 * 64)
    assert parts["scan"] == 4 * 45056 / 8
    assert parts["short_conv"] == 4 * 13 * 128
    assert parts["attention_projections"] == 2 * 64 * 128 * 2 \
        + 2 * 2 * 64 * 16
    assert parts["router"] == 4 * 2 * 64 * 32
    assert parts["held_experts"] == 4 * 1.5 * 2 * 2 * 64 * 32
    assert parts["shared_expert"] == 4 * 2 * 2 * 64 * 64
    assert parts["head"] == 2 * 64 * 256
    assert c.train_flops_per_token(cfg, 32, 1.5) == 3 * sum(parts.values())


def test_costs_of_the_configuration():
    """At the published widths: the scan's least work is BYTES (a chunked
    scan is bandwidth-bound on a v5e), a grouped product's too (8 groups of
    ~192 rows against 80 MB of weights); the issue's active-parameter
    arithmetic."""
    _, _, cfg, _, _, _ = harness.Files().cell(CELL)
    c, peaks = costs_ssd_share, costs.peaks_for("TPU v5 lite")
    Q, H, Pd, G, N = 128, 64, 64, 8, 128
    assert c.scan_flops_a_chunk(cfg) == 2 * Q * Q * N * G \
        + 2 * Q * Q * Pd * H + 4 * Q * Pd * N * H
    assert c.chunks(cfg) == 32
    assert c.scan_bytes(cfg, True) / peaks["hbm_bytes_per_s"] \
        > c.scan_flops(cfg, True) / peaks["bf16_flops_per_s"]
    assert c.scan_least_seconds_of(cfg, True, peaks) \
        == 4 * c.scan_least_seconds(cfg, True, peaks)
    rows = 192 * 8
    weights = 8 * 2688 * 1856 * 2
    assert c.expert_layer_least_seconds(cfg, rows, True, peaks) \
        == pytest.approx(6 * (rows * (2688 + 1856) * 2 + weights)
                         / peaks["hbm_bytes_per_s"])
    # 6 x active parameters a token: the issue's 7.8 TFLOP at 4096 tokens
    # counts the table's lookup as a product; without it
    per_token = c.train_flops_per_token(cfg, 4096, 6 * 8 / 128)
    assert 1.2e9 < per_token < 2.1e9


def _made_obs(cfg, steps=20):
    """A made observation: seconds by scope as a traced window of `steps`
    steps would give them."""
    by_scope = {
        "mamba/norm/rms_norm": 0.01,
        "mamba/in_proj/mul": 0.30,
        "mamba/conv/short_conv/taps/fusion": 0.02,
        "mamba/conv/short_conv_grad/gate/fusion": 0.03,
        "mamba/scan/ssd_scan/chunks/dot_general": 0.10,
        "mamba/scan/ssd_scan/states/while": 0.02,
        "mamba/scan/ssd_scan_grad/outputs/dot_general": 0.28,
        "mamba/gated_norm/rms_norm": 0.04,
        "mamba/out_proj/mul": 0.12,
        "attn/norm/rms_norm": 0.01,
        "attn/causal_attention/flash_fwd": 0.05,
        "attn/causal_attention_grad/flash_dkv": 0.07,
        "attn/causal_attention_grad/flash_dq": 0.04,
        "moe/moe_ffn/route/dot_general": 0.02,
        "moe/moe_ffn/dispatch/gather": 0.03,
        "moe/moe_ffn/combine/gather": 0.03,
        "moe/moe_ffn/grouped/relu_sq/grouped_matmul": 0.05,
        "moe/moe_ffn/grouped/grouped_matmul": 0.05,
        "moe/moe_ffn_grad/grouped/relu_sq_grad/grouped_matmul_nt": 0.05,
        "moe/moe_ffn_grad/grouped/grouped_matmul_nt": 0.05,
        "moe/moe_ffn_grad/grouped/relu_sq/grouped_matmul_tn": 0.05,
        "moe/moe_ffn_grad/grouped/grouped_matmul_tn": 0.05,
        "moe/shared/mul": 0.25,
        "embed/lookup_table_grad/row_tile_sum": 0.02,
        "lm_head/mul": 0.2}
    events = {k: steps * 4 for k in by_scope}
    busy = sum(by_scope.values())
    return dict(
        cfg=cfg, peaks=costs.peaks_for("TPU v5 lite"), chips=1,
        steps_in_window=steps, tokens_per_step=4096,
        rate_items_per_s=30000.0, held_rows_share=0.0625,
        held_rows_by_layer=[[1536] * 4] * steps,
        window_peak_bytes=13.0e9,
        scopes={"window_s": busy * 1.01, "busy_s": busy,
                "by_scope": by_scope, "events": events, "unscoped_ops": {}},
        device={"memory_peak_bytes": 14.5e9})


def test_ssd_readers_on_a_made_reduction():
    """Every `ssd.` reader returns a number on a made observation; each
    share is what the made seconds say; on an observation without the
    model's scopes (the parent's) each trace reader returns None and
    nothing is raised."""
    files = harness.Files()
    _, _, cfg, _, _, _ = files.cell(CELL)
    obs = _made_obs(cfg)
    got = {name: files.metric_reader("ssd." + name).read(obs)
           for name in SSD_METRICS}
    assert all(isinstance(v, float) for v in got.values()), got
    busy = obs["scopes"]["busy_s"]
    mixers = sum(s for k, s in obs["scopes"]["by_scope"].items()
                 if k.startswith("mamba/"))
    assert got["mixer_share"] == pytest.approx(100 * mixers / busy)
    assert got["scan_share"] == pytest.approx(100 * 0.40 / busy)
    assert got["conv_share"] == pytest.approx(100 * 0.05 / busy)
    assert got["gated_norm_share"] == pytest.approx(100 * 0.04 / busy)
    assert got["attention_share"] == pytest.approx(100 * 0.17 / busy)
    assert got["shared_expert_share"] == pytest.approx(100 * 0.25 / busy)
    c = costs_ssd_share
    assert got["scan_roofline"] == pytest.approx(
        100 * 20 * c.scan_least_seconds_of(cfg, True, obs["peaks"]) / 0.40)
    assert got["attention_roofline"] == pytest.approx(
        100 * 20 * c.attention_least_seconds_of(cfg, True, obs["peaks"])
        / 0.16)
    assert got["grouped_matmul_roofline"] == pytest.approx(
        100 * 20 * 4 * c.expert_layer_least_seconds(
            cfg, 1536, True, obs["peaks"]) / 0.30)
    assert 0 < got["grouped_matmul_roofline"] <= 100
    assert 0 < got["scan_roofline"] <= 100
    assert got["expert_cast_share"] == 0.0
    assert got["held_rows_share"] == 6.25
    assert got["peak_hbm_gb"] == 13.0
    assert 0 < got["model_flops_util"] <= 100
    # six kernels a layer and step are wanted: the made window holds them
    reader = files.metric_reader("ssd.grouped_matmul_roofline")
    assert reader.wanted_events(obs) == 20 * 24 and reader.note(obs) is None
    # a program from before the model: nothing to read, nothing raised
    before = dict(obs, held_rows_by_layer=None, scopes=dict(
        obs["scopes"], by_scope={"attn_full/causal_attention/flash_fwd": 1.0},
        events={"attn_full/causal_attention/flash_fwd": 80}))
    for name in ("mixer_share", "scan_share", "scan_roofline", "conv_share",
                 "gated_norm_share", "attention_share", "attention_roofline",
                 "shared_expert_share", "grouped_matmul_roofline"):
        assert files.metric_reader("ssd." + name).read(before) is None, name


def test_check_line_holds_the_recorded_lines_of_the_cell():
    """`python -m chipbench.check_line` on the lines the cell printed on
    the chip (my chip runs, PR 54: an untraced and a traced run)."""
    bench = harness.Files().bench()
    with open(RECORDED) as f:
        lines = [json.loads(ln)["line"] for ln in f if ln.strip()]
    assert {("busy_s" in ln["device"]) for ln in lines} == {False, True}
    for line in lines:
        assert line["workload"] == CELL and line["correct"]
        assert check_line.problems(line, bench) == []
    traced = next(ln for ln in lines if "busy_s" in ln["device"])
    assert set(traced["metrics"]) == set(check_line.listed(bench, CELL, True))
    for name in ("ssd.scan_roofline", "ssd.attention_roofline",
                 "ssd.grouped_matmul_roofline", "ssd.model_flops_util"):
        assert 0 < traced["metrics"][name]["value"] <= 100, name
    # the window holds a quarter of the chip and more
    assert traced["metrics"]["ssd.peak_hbm_gb"]["value"] > 0.25 * 16
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.check_line", RECORDED],
        cwd=harness.repo_root(), capture_output=True, text=True)
    assert done.returncode == 0, done.stdout + done.stderr


STUDY_OVERRIDE = {"config": dict(TINY["config"], amp="bfloat16"),
                  "traffic": {k: TINY["traffic"][k] for k in (
                      "doc_len_median", "doc_len_min", "doc_len_max")}}


def test_the_study_runs_every_variant_and_its_plants_reach_the_scan(
        capsys, tmp_path, monkeypatch):
    """The study's machinery at a tiny size on the CPU under bf16 AMP:
    every variant builds, runs and is judged; `state_bf16` and
    `decays_bf16` each move what the op-alone check reads (the plants reach
    `parallel/ssd.py` from outside; `state_one_pass` asks the MXU for fewer
    passes, which XLA:CPU has none of: the same numbers here); `masters`
    moves the update's. Whether a limit set at the published widths is
    crossed at this size says nothing: the chip's table is PERF.md's."""
    from chipbench import lower_precision_lm_ssd_share as study

    monkeypatch.chdir(tmp_path)
    study.main(["--seeds", "3", "--override", json.dumps(STUDY_OVERRIDE)])
    rows = {r["variant"]: r for r in map(
        json.loads, capsys.readouterr().out.strip().splitlines())}
    assert set(rows) == set(study.VARIANTS)
    stated = rows["stated"]["compared"]
    # the op alone in float32 stands at the recurrence as stated and three
    # orders away with its state or its decays in bf16
    assert stated["scan_f32_state_rms"][0] < 1e-5
    for name in ("state_bf16", "decays_bf16"):
        got = rows[name]["compared"]
        assert got["scan_f32_state_rms"][0] > 3e-4, name
        assert got["scan_f32_op_rms"][0] > 30 * stated["scan_f32_op_rms"][0]
        assert {"scan_f32_op_rms", "scan_f32_state_rms"} \
            & set(rows[name]["failed"]), name
    assert rows["masters"]["compared"]["update:w_in"][0] \
        > 10 * stated["update:w_in"][0]
    assert os.path.exists("chiprun_out/lower_precision_lm_ssd_share.jsonl")
