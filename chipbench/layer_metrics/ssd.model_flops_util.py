"""Model FLOP/s utilization of the cell: operations the forward and
backward passes need for a token (`chipbench/costs_ssd_share.py`: the
mixers' projections, the scan's matrix products at the stated chunk of 128
and the convolution's few operations an element, attention over the
triangle with its projections, the HELD experts of each expert layer over
the rows they really received (the mean over the window's steps and layers
of `RowsHeld`; two products an expert), the shared expert, the routers
over 128, the head; nothing recomputed is counted) x tokens/s of the
window, over chips x the table's bf16 peak."""

from chipbench import costs_ssd_share as costs


def read(obs):
    by_layer = obs.get("held_rows_by_layer")
    if not obs.get("rate_items_per_s") or not by_layer:
        return None
    cfg = obs["cfg"]
    rows = sum(map(sum, by_layer)) / (len(by_layer) * len(by_layer[0]))
    per_token = costs.train_flops_per_token(
        cfg, cfg["sequence_length"], rows / obs["tokens_per_step"])
    return (100.0 * per_token * obs["rate_items_per_s"]
            / (obs["chips"] * obs["peaks"]["bf16_flops_per_s"]))
