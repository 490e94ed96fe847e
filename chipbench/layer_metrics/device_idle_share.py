"""% of the traced window in which no operation ran on the device (union
of the operations' intervals, averaged over the chips)."""


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
