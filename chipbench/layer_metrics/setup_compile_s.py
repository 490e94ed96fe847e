"""Seconds JAX spent in backend compile requests during set-up, whether
compiled or loaded from the persistent cache (`backend_compile` duration
events, as chip_smoke.py counts them)."""


def read(obs):
    sc = obs.get("setup_compile")
    return sc["seconds"] if sc else None
