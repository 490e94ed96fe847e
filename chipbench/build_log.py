"""The program's build log (`paddle_tpu.cache.build_log`, PR 51) as the
`setup_*` readers of `chipbench/layer_metrics/` see it: the records of the
compiled-step builds that STARTED before the window opened, each with the
seconds of its phases (`verify`, `digest`, `l2_load`, `trace`, `lower`,
`backend`, `export`, `self`: they tile the build's wall) on `perf_counter`,
the clock of `obs["t_open"]`.

A program that keeps no build log (a checkout older than PR 51 under these
files) has nothing here: `records` gives None, and the readers give 0.0,
their `note` saying so, because `harness.run_cell` prints no line at all
where a reader of a listed metric gives None, and a checkout without the
log must still print its line.
"""

NO_LOG = "the program keeps no build log (paddle_tpu.cache.build_log)"
# a build's seconds that are neither JAX's trace, its lowering nor the
# backend
SELF = ("verify", "digest", "l2_load", "export", "self")


def records(obs):
    """The build records that began before the window, oldest first; None
    where the program has no build log."""
    try:
        from paddle_tpu.cache import build_log
    except ImportError:
        return None
    return [r for r in build_log() if r["t0"] < obs["t_open"]]


def seconds(obs, phases):
    """The seconds of `phases` summed over the set-up's builds; 0.0 where
    they hold none, and where there is no log."""
    return float(sum(r["phases"].get(p, 0.0) for r in records(obs) or ()
                     for p in phases))


def table(obs):
    """What standard error and `chipbench_detail.layer_metric_notes` carry
    of every build before the window."""
    got = records(obs)
    if got is None:
        return {"build_log": NO_LOG}
    keys = ("name", "kind", "fingerprint", "iters", "level", "cause",
            "persistent_hit", "phases", "key_diff", "nested_traces")
    return {"builds": [
        dict({k: r[k] for k in keys if k in r}, wall_s=r["t1"] - r["t0"])
        for r in got]}
