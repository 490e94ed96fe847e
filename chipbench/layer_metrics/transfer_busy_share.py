"""The busiest transfer lane's % of the window inside `datapipe.transfer`
(`device_put` of a chunk and the wait for the copy)."""

from chipbench import spans


def read(obs):
    by = spans.shares(obs, "datapipe.transfer")
    return max(by.values()) if by else None
