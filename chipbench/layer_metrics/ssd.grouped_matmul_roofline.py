"""% of their roofline the grouped products reached, with the `relu_sq`
epilogues in them (an UN-GATED expert: up and down, SIX kernels a layer
and step), at K 2688 / F 1856 and ~192 rows a group over 8 held groups:
the least time of the six products an expert layer makes over the rows ITS
held experts received in that step
(`costs_ssd_share.expert_layer_least_seconds`, summed over every expert
layer and every step of the window: `RowsHeld` of each is fetched) over
the seconds of the program's Pallas kernels in the traced window, found by
their names as `grouped_matmul_roofline.py` finds them (the `relu_sq` /
`relu_sq_grad` scopes stand before the kernels' names on the path). Six a
layer and step are wanted; another count is reported and does not erase
the metric (`grouped_matmul_roofline.seconds_of_the_kernels`)."""

from chipbench import costs_ssd_share as costs
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
            len(by_layer[0]) != costs.expert_layers(obs["cfg"]):
        return None
    least = sum(costs.expert_layer_least_seconds(
        obs["cfg"], rows, True, obs["peaks"])
        for step in by_layer for rows in step)
    return 100.0 * least / spent
