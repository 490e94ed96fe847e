"""The two plain references against the system at a tiny size on the CPU,
in float32 (no AMP): here the two must agree closely, direction of every
update included; what bf16 AMP does to that is in chipbench/compare.py."""

import json
import os

import pytest

import paddle_tpu as fluid
from chipbench import compare, harness

HERE = os.path.dirname(__file__)


def _cfg(name):
    cfg = json.load(open(os.path.join(HERE, "..", "configs",
                                      name + ".json")))
    # 64 px keeps 2x2 pixels in the last stage; batch norm over fewer
    # values than that is too ill-conditioned to compare anything
    return dict(cfg, image_size=64, num_classes=10, amp=None)


@pytest.mark.parametrize("name", ["resnet50", "se_resnext50"])
def test_system_agrees_with_the_plain_reference(name):
    cfg = _cfg(name)
    builder = harness.load_module(os.path.join(HERE, "..", "configs",
                                               name + ".py"))
    r = compare.against_reference(fluid, cfg, builder, fluid.TPUPlace(0),
                                  seed=3, batch=8)
    assert r["parameters_compared"] > 150
    assert r["train_loss_err"] < 1e-3
    assert r["logits_err"] < 1e-3
    assert abs(r["update_norm_ratio_median"] - 1) < 0.01
    assert r["head_update_cos"] > 0.999
    assert r["update_cos_median"] > 0.9
    assert r["ok"]


def test_the_plan_lists_every_weighted_layer_of_the_program():
    from chipbench import programs

    for name in ("resnet50", "se_resnext50"):
        cfg = _cfg(name)
        builder = harness.load_module(os.path.join(HERE, "..", "configs",
                                                   name + ".py"))
        built = builder.build(fluid, cfg, 1)
        layers = programs.layers_in_program_order(built["test_prog"])
        weighted = [k for k, _ in layers if k != "bn"]
        plan = builder.reference.layer_plan(cfg)
        assert len(plan) == len(weighted)
        gb = built["prog"].global_block()
        ordered = builder.reference_order(layers, cfg)
        convs = [g[0] for k, g in ordered if k == "conv"]
        for entry, wname in zip([p for p in plan if p["kind"] == "conv"],
                                convs):
            shape = tuple(gb.vars[wname].shape)
            assert shape == (entry["cout"], entry["cin"] // entry["groups"],
                             entry["k"], entry["k"]), (name, wname)


def test_full_width_parameter_count_is_the_published_one():
    from chipbench import programs

    cfg = json.load(open(os.path.join(HERE, "..", "configs",
                                      "resnet50.json")))
    builder = harness.load_module(os.path.join(HERE, "..", "configs",
                                               "resnet50.py"))
    built = builder.build(fluid, cfg, 1)
    assert programs.param_count(built["prog"]) == cfg["parameters"] \
        == 25610152
