"""% of its roofline the `ssd_scan` op reached, forward and backward, over
the mixers of the window's steps: the least seconds of the WORK
(`costs_ssd_share.scan_least_seconds_of`: the larger of the least bytes, x,
B, C, dt in and y out a pass, the chunk-start states once each way, and of
the chunked form's matrix operations at the configuration's own
`chunk_size` of 128, C B^T once a group, at `peaks.json`; counted the same
whatever lowers the op) over the seconds of the operations under the op's
two scopes. What a lowering moves or computes more shows here as a share
below 100. None unless both scopes are in the trace."""

from chipbench import costs_ssd_share as costs
from chipbench import scopes


def read(obs):
    red, steps = obs.get("scopes"), obs.get("steps_in_window")
    if not red or not steps:
        return None
    fwd = sum(s for k, s in red["by_scope"].items()
              if scopes.in_scope(k, "ssd_scan")
              and not scopes.in_scope(k, "ssd_scan_grad"))
    bwd = scopes.seconds(red, "ssd_scan_grad")
    if not fwd or not bwd:
        return None
    least = costs.scan_least_seconds_of(obs["cfg"], True, obs["peaks"])
    return 100.0 * least * steps / (fwd + bwd)
