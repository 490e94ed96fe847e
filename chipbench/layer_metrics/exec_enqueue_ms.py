"""Median `dispatch` phase of the window's step spans, in ms: the call of
the compiled scan up to its return (the enqueue, under async dispatch)."""

from chipbench import spans


def read(obs):
    return spans.median_phase_ms(obs, ("dispatch",))
