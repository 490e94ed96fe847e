"""Median `write_back` phase of the window's step spans, in ms: unpacking
the packed state, `scope.set_var` over every state var, the health and
NaN checks."""

from chipbench import spans


def read(obs):
    return spans.median_phase_ms(obs, ("write_back",))
