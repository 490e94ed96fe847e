"""How many compiled-step builds the set-up made: the program's build
records before the window, the `retrace` ones among them, so a second
build of one program shows as +1. Its `note` is the table of those builds
(name, fingerprint, phases, key_diff, persistent_hit, nested_traces)."""

from chipbench import build_log


def read(obs):
    return float(len(build_log.records(obs) or ()))


def note(obs):
    return build_log.table(obs)
