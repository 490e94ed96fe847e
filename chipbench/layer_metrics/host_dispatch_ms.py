"""Median host time of one `Executor.run` / `ParallelExecutor.run` call up
to its return (the dispatch of one K-step chunk), in ms."""

import statistics


def read(obs):
    d = obs.get("host_dispatch_s")
    return statistics.median(d) * 1000.0 if d else None
