"""Peak device memory on the chip as the window closes, in GB
(`harness.memory_peak`: `peak_bytes_in_use + peak_bytes_reserved`, the
process's high-water mark): the kind `train_tokens_window_share` builds,
warms and times its program BEFORE the comparison runs, so this is what
the cell's traffic holds and not the comparison's float32 reference (the
whole process's mark, the comparison in it, stays in the line's
`device.memory_peak_bytes`)."""


def read(obs):
    peak = obs.get("window_peak_bytes")
    return peak / 1e9 if peak else None
