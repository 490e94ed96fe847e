"""Seconds of set-up inside JAX's trace of the compiled steps' own jits
(`phases.trace` of the program's build records before the window): the
program's own Python, `run_ops` lowering every Fluid op, the kernels'
wrappers."""

from chipbench import build_log


def read(obs):
    return build_log.seconds(obs, ("trace",))
