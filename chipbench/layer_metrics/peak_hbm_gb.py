"""Peak device memory in use on the fullest chip, in GB
(`memory_stats()["peak_bytes_in_use"]`, whole process)."""


def read(obs):
    peak = obs["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
