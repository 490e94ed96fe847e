"""Seconds of set-up inside the jaxpr -> MLIR lowering of the compiled
steps (`phases.lower` of the program's build records before the window):
where PERF.md section 7 row 44's two-valued term lives."""

from chipbench import build_log


def read(obs):
    return build_log.seconds(obs, ("lower",))
