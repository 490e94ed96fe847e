"""Seconds of the set-up's builds that are neither JAX's trace, its
lowering nor the backend: `verify`, `digest`, `l2_load`, `export` and
`self` (key building, closures, scope reads, other jits' compiles, the
first call's enqueue) of the program's build records before the window."""

from chipbench import build_log


def read(obs):
    return build_log.seconds(obs, build_log.SELF)
