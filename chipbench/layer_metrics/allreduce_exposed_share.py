"""% of the traced window during which a collective was in flight on the
worst chip and no compute operation ran there: the all-reduce time that
nothing hid."""


def read(obs):
    tr = obs.get("trace")
    if not tr or tr["chips"] < 2 or not tr["worst_chip_window_s"]:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["worst_chip_window_s"]
