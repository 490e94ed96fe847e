"""The raw 99th percentile of latency from due time, in ms: rare stalls
show here; recorded, not judged."""


def read(obs):
    r = obs.get("reading")
    return r.get("p99_ms") if r else None
