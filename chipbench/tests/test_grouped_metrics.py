"""The two readers of the program's grouped-matmul kernels
(`tokens.grouped_matmul_roofline`, `tokens.expert_other_share`) on a
synthetic reduction by scope, and the three accepted metrics of the expert
layer (`tokens.expert_matmul_roofline`, `.moe_dispatch_share`,
`.moe_share`) following the products from XLA's calls to those kernels."""

import pytest

from chipbench import costs, costs_lm, harness, scopes

CELL = "olmoe_1b_7b_train_packed4k"
FWD = "moe/moe_ffn/grouped/grouped_matmul"
DLHS = "moe/moe_ffn_grad/grouped_matmul_nt"
DRHS = "moe/moe_ffn_grad/grouped_matmul_tn"


def _obs(events, seconds=(0.012, 0.012, 0.014)):
    files = harness.Files()
    _, _, cfg, _, _, _ = files.cell(CELL)
    red = {"busy_s": 0.3,
           "by_scope": {"moe/moe_ffn": 0.01, "moe/moe_ffn_grad": 0.02,
                        "[xla]copy": 0.5},
           "events": {"moe/moe_ffn": 40}}
    for key, n, s in zip((FWD, DLHS, DRHS), events or (), seconds):
        red["by_scope"][key], red["events"][key] = s, n
    return files, {"scopes": red, "steps_in_window": 2, "cfg": cfg,
                   "tokens_per_step": 8192,
                   "peaks": costs.peaks_for("TPU v5 lite")}


def test_scope_keys_of_the_kernels_survive_both_directions():
    """What the v5e compile writes as op_name (tests/test_tpu_compile.py),
    through `scope_of`: the kernel's name stays in the key, forward, and
    backward under `transpose(...)` / `jvp(...)`."""
    assert scopes.scope_of(
        "jit(step)/moe/moe_ffn/grouped/grouped_matmul/pallas_call:") == FWD
    assert scopes.scope_of(
        "jit(multi)/while/body/moe/moe_ffn_grad/transpose(moe/moe_ffn_grad)"
        "/jvp(grouped)/grouped_matmul_nt/pallas_call:") == DLHS
    assert scopes.scope_of(
        "jit(step)/moe/moe_ffn_grad/transpose(moe/moe_ffn_grad)/"
        "jvp(grouped)/grouped_matmul_tn/pallas_call:") == DRHS


@pytest.mark.parametrize("events, found", [
    ((6, 6, 6), True), ((6, 6, 5), True), ((12, 6, 6), True),
    (None, False)])
def test_readers_read_whatever_the_count_of_kernel_events(events, found):
    """Nine a step and layer are wanted (18 of two steps); since PR 48
    another count no longer erases the metric, which the cell lists. MORE
    events are work beyond the least and their seconds stay; FEWER (a
    chunk across the trace's edge, dropped events) are made up at the mean
    of those seen before the work of ALL the steps is divided by them,
    and `expert_other_share` takes the seconds as seen, as the scopes'
    total it subtracts them from does. The reader notes the two counts
    where they differ; it writes nothing into `obs`."""
    files, obs = _obs(events)
    before = dict(obs)
    readers = {n: files.metric_reader("tokens." + n)
               for n in ("grouped_matmul_roofline", "expert_other_share")}
    got = {n: r.read(obs) for n, r in readers.items()}
    assert obs == before
    if not found:
        assert got == dict.fromkeys(got)
        assert readers["grouped_matmul_roofline"].note(obs) is None
        return
    least = costs_lm.expert_layer_least_seconds(
        obs["cfg"], 8192, True, obs["peaks"])
    seen = sum(events)
    assert got["grouped_matmul_roofline"] == pytest.approx(
        100 * least * 2 / (0.038 * max(1.0, 18 / seen)))
    assert 0 < got["grouped_matmul_roofline"] < 100
    assert got["expert_other_share"] == pytest.approx(
        100 * 0.03 / (0.03 + 0.038))
    assert readers["grouped_matmul_roofline"].note(obs) == (
        None if seen == 18 else {"grouped_kernel_events": {
            "got": seen, "wanted": 18, "steps_in_window": 2}})
    assert not hasattr(readers["expert_other_share"], "note")


def test_readers_read_nothing_from_the_parent_program():
    """XLA's `ragged-dot-none` calls are not these kernels: a trace of the
    program before PR 29 gives None, and raises nothing."""
    files, obs = _obs(None)
    key = "[xla]" + scopes.GROUPED_PRODUCT
    obs["scopes"]["by_scope"][key], obs["scopes"]["events"][key] = 0.05, 18
    for n in ("grouped_matmul_roofline", "expert_other_share"):
        assert files.metric_reader("tokens." + n).read(obs) is None
        assert files.metric_reader("tokens." + n).read(
            dict(obs, scopes=None)) is None


ACCEPTED = ("expert_matmul_roofline", "moe_dispatch_share", "moe_share")


def _accepted(files, obs):
    return {n: files.metric_reader("tokens." + n).read(obs)
            for n in ACCEPTED}


def test_accepted_metrics_read_the_parent_as_their_base_readers_do():
    """A trace with XLA's `ragged-dot-none` calls: the prefix-named readers
    give what `expert_matmul_roofline.py`, `moe_dispatch_share.py` and
    `moe_share.py` give."""
    files, obs = _obs(None)
    key = "[xla]" + scopes.GROUPED_PRODUCT
    obs["scopes"]["by_scope"][key], obs["scopes"]["events"][key] = 0.05, 18
    got = _accepted(files, obs)
    assert got == {n: files.metric_reader(n).read(obs) for n in ACCEPTED}
    assert got["moe_dispatch_share"] == pytest.approx(100 * 0.03 / 0.08)
    assert got["moe_share"] == pytest.approx(100 * 0.08 / 0.3)


def test_accepted_metrics_follow_the_products_to_the_kernels():
    """A trace with the program's kernels and no `ragged-dot-none`: the
    base readers find nothing, the prefix-named ones read the same
    quantities from the kernels."""
    files, obs = _obs((6, 6, 6))
    assert {n: files.metric_reader(n).read(obs) for n in ACCEPTED} == \
        dict.fromkeys(ACCEPTED)
    got = _accepted(files, obs)
    assert got["expert_matmul_roofline"] == files.metric_reader(
        "tokens.grouped_matmul_roofline").read(obs)
    assert got["moe_dispatch_share"] == pytest.approx(
        100 * 0.03 / (0.03 + 0.038))
    assert got["moe_share"] == pytest.approx(100 * (0.03 + 0.038) / 0.3)


def test_accepted_metrics_read_nothing_without_the_products():
    files, obs = _obs(None)
    assert _accepted(files, obs) == dict.fromkeys(ACCEPTED)
    assert _accepted(files, dict(obs, scopes=None)) == \
        dict.fromkeys(ACCEPTED)
