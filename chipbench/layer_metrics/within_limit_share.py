"""% of the window's requests answered within the traffic file's latency
limit, from due time; a failed request is outside it."""


def read(obs):
    r = obs.get("reading")
    return r.get("within_limit_share") if r else None
