"""Model FLOP/s utilization of a training cell whose model holds one
chip's share of each layer and mixes window with full attention layers:
operations the forward and backward passes need for a token
(`chipbench/costs_window_share.py`: a window layer's attention over its
BAND, a full layer's over the triangle, each at its own head count; the
HELD experts of each sparse layer over the rows they really received (the
mean over the window's steps and sparse layers of `RowsHeld`), the shared
expert, the dense MLP, the head; nothing recomputed is counted) x tokens/s
of the window, over chips x the table's bf16 peak."""

from chipbench import costs_window_share


def read(obs):
    by_layer = obs.get("held_rows_by_layer")
    if not obs.get("rate_items_per_s") or not by_layer:
        return None
    cfg = obs["cfg"]
    rows = sum(map(sum, by_layer)) / (len(by_layer) * len(by_layer[0]))
    per_token = costs_window_share.train_flops_per_token(
        cfg, cfg["sequence_length"], rows / obs["tokens_per_step"])
    return (100.0 * per_token * obs["rate_items_per_s"]
            / (obs["chips"] * obs["peaks"]["bf16_flops_per_s"]))
