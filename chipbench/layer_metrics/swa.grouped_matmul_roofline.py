"""% of their roofline the grouped products of the sparse layers reached,
in a model that holds a share of its experts: the least time of the nine
products a sparse layer makes over the rows ITS held experts received in
that step (`costs_window_share.expert_layer_least_seconds`, summed over
every sparse layer and every step of the window: `RowsHeld` of each is
fetched) over the seconds of the program's Pallas kernels in the traced
window, found by their names as `grouped_matmul_roofline.py` finds them.
The count wanted is `costs_window_share.grouped_kernels_per_step` a step
(nine a sparse layer); another count is reported and no longer erases the
metric (`grouped_matmul_roofline.seconds_of_the_kernels`, PR 48)."""

from chipbench import costs_window_share
from chipbench.layer_metrics.grouped_matmul_roofline import (
    events_note, seconds_of_the_kernels)


def wanted_events(obs):
    return (obs.get("steps_in_window") or 0) \
        * costs_window_share.grouped_kernels_per_step(obs["cfg"])


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
            len(by_layer[0]) != costs_window_share.sparse_layers(obs["cfg"]):
        return None
    least = sum(costs_window_share.expert_layer_least_seconds(
        obs["cfg"], rows, True, obs["peaks"])
        for step in by_layer for rows in step)
    return 100.0 * least / spent
