"""Median host time a step spends before the compiled scan is called:
the executor's `feed_encode`, `state_gather` and `cache_lookup` phases
(marker pop and feed stacking; state names, scope reads and the PackPlan
repack; fuse plan, cache key and the `CompileCache` look-up), summed per
step, over the window's step spans, in ms."""

from chipbench import spans


def read(obs):
    return spans.median_phase_ms(
        obs, ("feed_encode", "state_gather", "cache_lookup"))
