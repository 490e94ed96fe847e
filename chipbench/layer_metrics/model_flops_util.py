"""Model FLOP/s utilization: operations the forward and backward passes
need for an item (from the shapes, `chipbench/costs.py`; nothing
recomputed is counted) x items/s of the traced window, over chips x the
table's bf16 peak."""

from chipbench import costs


def read(obs):
    if not obs.get("rate_items_per_s") or not obs.get("train"):
        return None
    per_item = costs.step_flops(obs["plan"], 1, True)
    return (100.0 * per_item * obs["rate_items_per_s"]
            / (obs["chips"] * obs["peaks"]["bf16_flops_per_s"]))
