"""% of their roofline the expert layer's grouped products reached, where
they run as the program's own Pallas kernels: the least time of the nine
products a training step makes (`costs_lm.expert_layer_least_seconds`:
gate, up, down and each one's two gradients over the rows really routed;
operations bind) over the seconds of the kernels in the traced window.

The kernels are found by their names (`grouped_matmul` forward,
`grouped_matmul_nt` d lhs, `grouped_matmul_tn` d rhs): Pallas puts a
kernel's name on the op_name's path, so the scope key reads
`moe/moe_ffn/grouped/grouped_matmul` forward and
`moe/moe_ffn_grad/grouped_matmul_nt` backward. None where the trace holds none of them: a
program whose products run under another name (XLA's `ragged-dot-none`
before PR 29) has nothing to read here. Nine a step and layer are wanted;
another count is reported, not refused (`seconds_of_the_kernels`,
`events_note`, PR 48)."""

from chipbench import costs_lm, scopes

KERNELS = ("grouped_matmul", "grouped_matmul_nt", "grouped_matmul_tn")


def _seen(red):
    keys = [k for k in red["by_scope"] if scopes.in_scope(k, *KERNELS)]
    return (sum(red["by_scope"][k] for k in keys),
            sum(red["events"].get(k, 0) for k in keys))


def seconds_of_the_kernels(red, obs, want, for_all_wanted=False):
    """Seconds of the grouped kernels the traced window holds, whatever
    their number; None where it holds none or `want` (the events the
    window's steps should make) is unknown. Since PR 48 a count other than
    `want` no longer erases the metric (a cell lists it, and a line that
    lacks it is refused). MORE events than wanted are real work beyond the
    least (a step past the row bound, whose backward forms the products
    again): their seconds stay and LOWER a roofline share. With
    `for_all_wanted` (the roofline readers, which divide the least work of
    ALL the window's steps by this) FEWER events than wanted (a chunk
    across the trace's edge, events the profiler dropped) are made up at
    the mean of those seen, seconds x wanted / got: the work is not held
    against the seconds of a part of its kernels. The `*expert_other_share`
    readers take the seconds as seen: the scopes' total they subtract them
    from lacks the same events. The two counts: `events_note`."""
    if not want:
        return None
    seconds, got = _seen(red)
    if for_all_wanted and 0 < got < want:
        seconds *= want / got
    return seconds or None


def events_note(obs, want):
    """What a roofline reader's `note(obs)` hands the harness for the
    run's `chipbench_detail` and standard error: the kernel events the
    window holds beside those wanted, where they differ."""
    red = obs.get("scopes")
    got = _seen(red)[1] if red else 0
    if not want or not got or got == want:
        return None
    return {"grouped_kernel_events": {
        "got": got, "wanted": want,
        "steps_in_window": obs.get("steps_in_window")}}


def wanted_events(obs):
    """Nine a step and layer."""
    return (obs.get("steps_in_window") or 0) \
        * costs_lm.expert_products(True) * obs["cfg"]["num_hidden_layers"]


def kernel_seconds(red, obs, for_all_wanted=False):
    return seconds_of_the_kernels(red, obs, wanted_events(obs),
                                  for_all_wanted)


def note(obs):
    return events_note(obs, wanted_events(obs))


def read(obs):
    red = obs.get("scopes")
    spent = kernel_seconds(red, obs, True) if red else None
    if not spent:
        return None
    least = obs["cfg"]["num_hidden_layers"] * \
        costs_lm.expert_layer_least_seconds(
            obs["cfg"], obs["tokens_per_step"], True, obs["peaks"])
    return 100.0 * least * obs["steps_in_window"] / spent
