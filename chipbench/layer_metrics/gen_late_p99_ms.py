"""99th percentile of how late the generator sent a request, in ms, on
its own clock. A run in which this is not small beside the latencies it
measures is not a measurement (PERF.md says what small came to)."""


def read(obs):
    r = obs.get("reading")
    return r.get("gen_late_p99_ms") if r else None
