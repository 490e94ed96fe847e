"""% of their roofline the grouped products reached, with the SiLU
epilogues in them, at K 2048 / F 512 and ~160 rows a group over 32 held
groups: the least time of the nine products a layer makes over the rows
ITS held experts received in that step
(`costs_delta_share.expert_layer_least_seconds`, summed over every layer
and every step of the window: `RowsHeld` of each is fetched) over the
seconds of the program's Pallas kernels in the traced window, found by
their names as `grouped_matmul_roofline.py` finds them. Nine a layer
and step are wanted; another count is reported and no longer erases the
metric (`grouped_matmul_roofline.seconds_of_the_kernels`, PR 48)."""

from chipbench import costs_delta_share as costs
from chipbench.layer_metrics.grouped_matmul_roofline import (
    events_note, seconds_of_the_kernels)


def wanted_events(obs):
    return (obs.get("steps_in_window") or 0) \
        * costs.grouped_kernels_per_step(obs["cfg"])


def kernel_seconds(red, obs, for_all_wanted=False):
    return seconds_of_the_kernels(red, obs, wanted_events(obs),
                                  for_all_wanted)


def note(obs):
    return events_note(obs, wanted_events(obs))


def read(obs):
    red = obs.get("scopes")
    spent = kernel_seconds(red, obs, True) if red else None
    by_layer = obs.get("held_rows_by_layer")
    if not spent or not by_layer:
        return None
    if len(by_layer) != obs["steps_in_window"] or \
            len(by_layer[0]) != obs["cfg"]["num_hidden_layers"]:
        return None
    least = sum(costs.expert_layer_least_seconds(
        obs["cfg"], rows, True, obs["peaks"])
        for step in by_layer for rows in step)
    return 100.0 * least / spent
