"""The `train_tokens` kind end to end on the CPU rehearsal path at a tiny
override of the `olmoe_1b_7b` configuration: counts and control flow only
(metrics present, no compile in the window, every token routed, the
reference comparison wired through); no number here is a timing. And the
token generator, and the reduction of a trace by scope on the recorded
chip trace `chipbench/data/small.xplane.pb`."""

import io
import json
import os

import numpy as np
import pytest

from chipbench import harness, scopes

HERE = os.path.dirname(__file__)
CELL = "olmoe_1b_7b_train_packed4k"
TINY = {"config": {"hidden_size": 64, "num_attention_heads": 4,
                   "num_key_value_heads": 4, "num_experts": 8,
                   "num_experts_per_tok": 2, "intermediate_size": 32,
                   "vocab_size": 256, "sequence_length": 32,
                   "eos_token_id": 255,
                   # float32: the comparison's limits are set at the
                   # published widths, and 32 tokens of width 64 do not
                   # average bf16 rounding as 4096 of width 2048 do
                   "amp": None},
        "traffic": {"steps_per_chunk": 2, "distinct_chunks": 3,
                    "trace_chunks": 2, "doc_len_median": 10,
                    "doc_len_min": 2, "doc_len_max": 32}}


def _run(trace):
    out = io.StringIO()
    line = harness.run_cell(CELL, seed=2 ** 31 + 29, seconds=2.0,
                            trace=trace, rehearsal=True, override=TINY,
                            files=harness.Files(), out=out)
    return line, [json.loads(v) for v in out.getvalue().splitlines()]


def test_tokens_cell_untraced():
    line, lines = _run(False)
    assert set(line["metrics"]) == {"train_items_per_s", "setup_s"}
    assert line["checks"] == {"reference": True, "losses_finite": True,
                              "window_compiles_zero": True,
                              "every_token_routed": True}
    assert line["correct"] and line["failed"] == 0
    detail, ref = lines[1]["chipbench_detail"], lines[1]["reference"]
    assert detail["distinct_chunks"] == 3 and detail["chunks_handed"] >= 4
    assert detail["documents_in_chunks"] > 10
    # float32 on the CPU: the system routes as the reference does
    assert ref["routing"]["flipped"] == 0 and ref["routing"]["sets_of_k"]
    assert set(ref["by_param"]) == {
        "head", "router", "expert_gate", "expert_up", "expert_down", "wq",
        "norm_scale", "embedding"}
    names = [n for n, _ in lines[0]["chipbench_setup"]["items"]]
    assert names.index("reference_comparison") < names.index("program_build")


def test_tokens_cell_traced():
    line, _ = _run(True)
    # the scope-read metrics need a device plane, which XLA:CPU does not
    # write: their readers return None and the line leaves them out
    assert {"host_dispatch_ms", "tokens.model_flops_util",
            "expert_load_max_over_mean",
            "setup_compile_s"} <= set(line["metrics"])
    assert not {"tokens.moe_share", "tokens.expert_matmul_roofline",
                "tokens.attention_roofline"} & set(line["metrics"])
    assert line["metrics"]["expert_load_max_over_mean"]["value"] >= 1
    assert line["checks"]["window_compiles_zero"]
    assert line["checks"]["every_token_routed"]
    assert line["attempted"] == 2


def test_token_rows_are_packed_documents():
    files = harness.Files()
    _, _, cfg, traffic, _, kind = files.cell(CELL)
    tok, lab, docs = kind.token_rows(cfg, traffic, 2 ** 31 + 5, 8)
    S, eos = cfg["sequence_length"], cfg["eos_token_id"]
    assert tok.shape == lab.shape == (8, S) and tok.dtype == np.int32
    # labels are the next token, across the row boundary too
    np.testing.assert_array_equal(tok.ravel()[1:], lab.ravel()[:-1])
    assert 0 <= tok.min() and tok.max() < cfg["vocab_size"]
    ends = np.flatnonzero(tok.ravel() == eos)
    assert len(ends) == docs or len(ends) == docs - 1   # last label's EOS
    lengths = np.diff(ends) - 1
    assert traffic["doc_len_min"] <= lengths.min()
    assert lengths.max() <= traffic["doc_len_max"]
    assert 350 <= np.median(lengths) <= 1000            # median 600
    # Zipf: the commonest id is far commoner than the median id
    counts = np.sort(np.bincount(tok.ravel()[tok.ravel() != eos]))[::-1]
    assert counts[0] > 50 * max(1, counts[len(counts) // 2])
    again, _, _ = kind.token_rows(cfg, traffic, 2 ** 31 + 5, 8)
    np.testing.assert_array_equal(tok, again)
    other, _, _ = kind.token_rows(cfg, traffic, 2 ** 31 + 6, 8)
    assert (tok != other).mean() > 0.5


@pytest.mark.parametrize("tf_op, want", [
    ("jit(multi)/while/body/moe/moe_ffn/jit(argsort)/sort:", "moe/moe_ffn"),
    ("jit(step)/optimizer/adam/mul", "optimizer/adam"),
    ("jit(step)/lm_head/softmax_with_cross_entropy_grad/"
     "transpose(jvp(jit(take_along_axis)))/scatter-add",
     "lm_head/softmax_with_cross_entropy_grad"),
    ("jit(step)/convnet/conv_general_dilated:", "convnet"),
    ("jit(step)/reduce_sum", ""), ("", ""), (None, "")])
def test_scope_of(tf_op, want):
    assert scopes.scope_of(tf_op) == want


def test_scopes_of_a_recorded_chip_trace():
    """PR 23's recorded trace ran a convolution network under
    `jax.named_scope("convnet")`: its fusions are filed there, what XLA
    made itself under `[xla]<name>`, and the seconds add up to the self
    times of the whole line."""
    red = scopes.reduce_file(os.path.join(os.path.dirname(HERE), "data",
                                          "small.xplane.pb"))
    assert red["by_scope"]["convnet"] > 0
    assert scopes.seconds(red, "convnet") == red["by_scope"]["convnet"]
    assert any(k.startswith("[xla]copy") for k in red["by_scope"])
    assert 0 < scopes.unscoped_share(red) < 100
    assert sum(red["by_scope"].values()) <= red["busy_s"] * 1.0001
    obs = {"steps_in_window": 1, "cfg": {"num_hidden_layers": 1}}
    assert scopes.expert_layer_seconds(red, obs) is None


@pytest.mark.parametrize("products, found", [(18, True), (17, False),
                                             (None, False)])
def test_expert_layer_readers_read_nothing_without_every_product(products,
                                                                 found):
    """The grouped products carry XLA's name, not a scope of the program:
    unless the window holds nine a step and layer, the three metrics that
    count them read nothing, not the op's own scopes alone."""
    from chipbench import costs

    files = harness.Files()
    _, _, cfg, _, _, _ = files.cell(CELL)
    key = "[xla]" + scopes.GROUPED_PRODUCT
    red = {"busy_s": 0.3, "by_scope": {"moe/moe_ffn": 0.01,
                                       "moe/moe_ffn_grad": 0.02},
           "events": {"moe/moe_ffn": 40}}
    if products:
        red["by_scope"][key], red["events"][key] = 0.05, products
    obs = {"scopes": red, "steps_in_window": 2, "cfg": cfg,
           "tokens_per_step": 8192, "peaks": costs.peaks_for("TPU v5 lite")}
    got = {name: files.metric_reader("tokens." + name).read(obs)
           for name in ("moe_share", "moe_dispatch_share",
                        "expert_matmul_roofline")}
    if not found:
        assert got == dict.fromkeys(got)
        return
    assert got["moe_share"] == pytest.approx(100 * 0.08 / 0.3)
    assert got["moe_dispatch_share"] == pytest.approx(100 * 0.03 / 0.08)
    assert 0 < got["expert_matmul_roofline"] < 100


def test_lower_precision_study_tells_the_variants_apart(tmp_path,
                                                        monkeypatch, capsys):
    """The study's machinery at a tiny size on the CPU under bf16 AMP:
    bf16 master weights fail the update check and a bf16 loss the loss
    check, which the system as stated passes. (The limits that need the
    published widths to average the rounding out are not asserted.)"""
    from chipbench import lower_precision_lm

    monkeypatch.chdir(tmp_path)
    tiny = dict(TINY, config=dict(TINY["config"], amp="bfloat16"))
    lower_precision_lm.main([
        "--seeds", str(2 ** 31 + 31), "--variants", "stated", "loss",
        "masters", "--override", json.dumps(tiny)])
    failed = {d["variant"]: set(d["failed"]) for d in map(
        json.loads, (tmp_path / "chiprun_out" /
                     "lower_precision_lm.jsonl").read_text().splitlines())}
    assert not failed["stated"] & {"loss", "update"}
    assert "loss" in failed["loss"] and "update" not in failed["loss"]
    assert "update" in failed["masters"] and "loss" not in failed["masters"]
