"""% of dispatched rows that were bucket padding (`server.stats()`,
window only)."""


def read(obs):
    return obs.get("pad_share")
