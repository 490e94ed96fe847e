"""% of the window the consumer spent inside the feeder's `next_staged`
waiting for a chunk (`datapipe.next`, the program's own span): the inside
twin of `input_wait_share`."""

from chipbench import spans


def read(obs):
    by = spans.shares(obs, "datapipe.next")
    return sum(by.values()) if by else None
