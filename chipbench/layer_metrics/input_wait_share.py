"""% of the window the harness's loop spent inside the feeder's `next`
(chipbench's own clock): how long the step waited for its input."""


def read(obs):
    if obs.get("input_wait_s") is None or not obs.get("window_s"):
        return None
    return 100.0 * obs["input_wait_s"] / obs["window_s"]
