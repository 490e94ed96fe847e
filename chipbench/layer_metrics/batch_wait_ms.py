"""Mean time a request waited in the server's queue before the batcher
picked it up, in ms (`serve_request_phase_ms{phase=queue}`, window only)."""


def read(obs):
    return obs.get("batch_wait_ms")
