"""% of the resident chunk rate that the pipe delivered: the traced run's
rate through datapipe over the rate of the SAME compiled scan on chunks
already on the device (a short second window of the same run)."""


def read(obs):
    keep = obs.get("keepup")
    if not keep or not keep.get("median_items_per_s"):
        return None
    return 100.0 * obs["rate_items_per_s"] / keep["median_items_per_s"]
