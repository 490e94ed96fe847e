"""Median `datapipe.handoff` of the window's items, in ms: from the parent's
`put` of a record on a decode worker's task queue to that worker's `get`
returning it (pickling, the pipe, and the queue behind the worker)."""

from chipbench import spans


def read(obs):
    return spans.median_ms(obs, "datapipe.handoff")
