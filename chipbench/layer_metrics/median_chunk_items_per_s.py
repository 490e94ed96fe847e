"""The median of the window's chunk rates (one reading per K-step chunk:
its items over the time since the chunk before it completed). Steadier
than the window's own rate, which counts every stall; it stands beside the
end-to-end rate, never in its place: where the pipe alternates between
bursts and stalls the median reads one of the two modes."""


def read(obs):
    r = obs.get("reading")
    return r.get("median_items_per_s") if r else None
