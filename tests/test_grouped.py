"""The grouped-matmul Pallas kernels (paddle_tpu/parallel/grouped.py) in
interpret mode on the CPU, against `lax.ragged_dot`; `moe_ffn` through
either lowering; the fall-back for shapes the kernels refuse."""

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu as fluid
from paddle_tpu.ops import lm_ops
from paddle_tpu.parallel import grouped

N, K, M, TM = 256, 128, 256, 64

# group sizes over 256 rows walked in row tiles of 64
LAYOUTS = {
    "even": [64, 64, 64, 64],
    "one_expert_owns_every_row": [0, 256, 0, 0],
    "empty_experts_first_middle_last": [0, 100, 0, 156, 0],
    "boundaries_inside_tiles": [3, 5, 7, 9, 11, 13, 15, 193],
    "a_group_exactly_one_tile": [32, 32, 64, 128],
    "two_boundaries_in_one_tile": [70, 10, 20, 156],
}


def _kernel_calls(fn, *args):
    """How many Pallas kernels a run of `fn(*args)` calls: the
    `pallas_call` equations of its jaxpr, those of a jaxpr that several
    call sites share (the kernels are traced once a signature, under
    `jax.jit`) counted at each site."""
    def count(jaxpr):
        return sum(1 if eqn.primitive.name == "pallas_call" else sum(
            count(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
            for eqn in jaxpr.eqns)

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


@functools.lru_cache(maxsize=None)
def _both(layout, dtype, block_rows):
    """(kernels, ragged_dot): each (out, d lhs, d rhs) as float32 numpy.
    The forward has all of K in one tile (no float32 scratch), d lhs two
    K tiles (sums in scratch); `block_rows` 16 walks a tile that a
    boundary crosses in four blocks, 128 as one."""
    was, grouped._BLOCK_ROWS = grouped._BLOCK_ROWS, block_rows
    try:
        return _compute(layout, dtype)
    finally:
        grouped._BLOCK_ROWS = was


def _compute(layout, dtype):
    counts = jnp.asarray(LAYOUTS[layout], jnp.int32)
    rs = np.random.default_rng(len(layout))
    lhs, rhs, g = (jnp.asarray(rs.standard_normal(s), dtype) for s in (
        (N, K), (len(counts), K, M), (N, M)))
    tiles = (TM, (128, 128), (128, 128), (128, 128))
    results = []
    for t in (tiles, None):
        out, vjp = jax.vjp(lambda a, b: grouped.grouped_matmul(
            a, b, counts, None, t), lhs, rhs)
        results.append([np.asarray(x, np.float32) for x in (out, *vjp(g))])
    return results


@pytest.mark.parametrize("which", ["forward", "d_lhs", "d_rhs"])
@pytest.mark.parametrize("block_rows", [16, 128])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernels_match_ragged_dot(layout, dtype, block_rows, which):
    """Forward (`_gmm`), d lhs (the same kernel, the weight contracted
    over its last dimension) and d rhs (`_tgmm`) equal `lax.ragged_dot`
    and its vjp: to float32 rounding in float32, to one bf16 rounding of
    the float32 sums in bf16. An empty group's d rhs is zeros, written."""
    i = ["forward", "d_lhs", "d_rhs"].index(which)
    ours, ref = (r[i] for r in _both(layout, dtype, block_rows))
    assert ours.shape == ref.shape and np.all(np.isfinite(ours))
    tol = 2e-6 if dtype == "float32" else 2 ** -8
    assert np.max(np.abs(ours - ref)) <= tol * np.max(np.abs(ref))
    if which == "d_rhs":
        for e, c in enumerate(LAYOUTS[layout]):
            assert c or not ours[e].any()


@pytest.mark.parametrize("tm", [32, 64, 128])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_visits_cover_every_group_once_per_tile(layout, tm):
    """The work list: each (group, tile) pair with rows in common appears
    exactly once, groups and tiles both in order, N / tm + boundaries
    inside tiles visits; with `empty_groups` each empty group once more."""
    counts = LAYOUTS[layout]
    ends = np.cumsum(counts)
    want = [(g, t) for g, (lo, hi) in enumerate(zip(ends - counts, ends))
            for t in range(N // tm) if lo < hi and lo < (t + 1) * tm > t * tm < hi]
    (offsets, gids, tids), n = grouped.visits(
        jnp.asarray(counts, jnp.int32), N, tm, False)
    assert list(np.asarray(offsets)) == [0, *ends]
    assert list(zip(np.asarray(gids)[:n], np.asarray(tids)[:n])) == want
    (_, gids, tids), n_t = grouped.visits(
        jnp.asarray(counts, jnp.int32), N, tm, True)
    assert int(n_t) == len(want) + counts.count(0) <= len(gids)
    assert list(np.asarray(gids)[:n_t]) == sorted(
        [g for g, _ in want] + [g for g, c in enumerate(counts) if not c])
    assert np.all(np.diff(np.asarray(tids)[:n_t]) >= 0)


def test_a_given_product_is_not_computed_again():
    """`out=`: the value is returned as it is and the call carries the
    gradients alone: the vjp holds the two backward kernels and no
    forward one."""
    counts = jnp.asarray(LAYOUTS["even"], jnp.int32)
    lhs, rhs = jnp.ones((N, K)), jnp.ones((4, K, M))
    tiles = (TM, (128, 128), (128, 128), (128, 128))
    saved = grouped.grouped_matmul(lhs, rhs, counts, None, tiles)

    def grads(a, b, out):
        return jax.vjp(lambda x, y: grouped.grouped_matmul(
            x, y, counts, out, tiles), a, b)[1](jnp.ones((N, M)))

    assert _kernel_calls(grads, lhs, rhs, saved) == 2
    assert _kernel_calls(lambda a, b: grads(a, b, None), lhs, rhs) == 3
    for ours, ref in zip(grads(lhs, rhs, saved), grads(lhs, rhs, None)):
        np.testing.assert_array_equal(ours, ref)


def test_a_given_mlp_is_not_computed_again(monkeypatch):
    """`grouped_mlp(saved=)`: a, b and ys are returned as they are and the
    vjp holds the six backward kernels alone; without them, all nine."""
    monkeypatch.setattr(grouped, "on_tpu", lambda: True)
    monkeypatch.setattr(grouped, "ROW_TILES", (TM,))
    counts = jnp.asarray(LAYOUTS["two_boundaries_in_one_tile"], jnp.int32)
    xs, gate, up, down, g = _mlp_operands(4, "float32")

    def grads(saved, *w):
        out, vjp = jax.vjp(lambda *w: grouped.grouped_mlp(
            *w, counts, saved)[0], *w)
        return vjp(g)

    ys, a, b = grouped.grouped_mlp(xs, gate, up, down, counts)
    assert _kernel_calls(grads, (a, b, ys), xs, gate, up, down) == 6
    assert _kernel_calls(lambda *w: grads(None, *w),
                         xs, gate, up, down) == 9
    for got in grouped.grouped_mlp(xs, gate, up, down, counts, (a, b, ys)):
        assert got is a or got is b or got is ys
    for ours, ref in zip(grads((a, b, ys), xs, gate, up, down),
                         grads(None, xs, gate, up, down)):
        np.testing.assert_array_equal(ours, ref)


@pytest.mark.parametrize("n_rows,k,m,dtype,want", [
    (65536, 2048, 1024, "bfloat16", "kernels"),
    (65536, 1024, 2048, "bfloat16", "kernels"),
    (1024, 256, 384, "float32", "kernels"),
    (16384, 3584, 1024, "bfloat16", "kernels"),     # K whole at 7 KiB a row
    (16384, 1024, 3584, "bfloat16", "kernels"),
    (16384, 7168, 1024, "bfloat16", "kernels"),     # 14 KiB a row: split
    (65536, 2048, 512, "bfloat16", "kernels"),      # experts of width 512:
    (65536, 512, 2048, "bfloat16", "kernels"),      # gate / up, and down
    (24576, 2560, 768, "bfloat16", "kernels"),      # K 2560 / F 768, the
    (24576, 768, 2560, "bfloat16", "kernels"),      # row bound's rows
    (49152, 2560, 768, "float32", "kernels"),       # 10 KiB a row: split
    (65536 + 128, 2048, 1024, "bfloat16", "kernels"),
    (65536 + 64, 2048, 1024, "bfloat16", None),     # rows no tile divides
    (65536, 2048 + 64, 1024, "bfloat16", None),     # K not of 128
    (65536, 2048, 1000, "bfloat16", None),          # M not of 128
])
def test_tiles_are_chosen_from_the_shapes(n_rows, k, m, dtype, want):
    tiles = grouped.tiles_for(n_rows, k, m, dtype)
    assert grouped.takes(n_rows, k, m) == (want is not None)
    if want is None:
        assert tiles is None
        return
    tm, fwd, dlhs, drhs = tiles
    assert n_rows % tm == 0 and tm in grouped.ROW_TILES
    for (tk, tn), (kk, mm) in zip((fwd, dlhs, drhs),
                                  ((k, m), (m, k), (k, m))):
        assert kk % tk == 0 and mm % tn == 0 and tk % 128 == 0 == tn % 128
    # the forward and d lhs keep the whole of a contraction this short in
    # one tile: a group's weight tile is then fetched once, not per visit
    item = jnp.dtype(dtype).itemsize
    if k * item <= 8192:
        assert fwd[0] == k
    else:
        assert fwd[0] * item <= 8192
    if m * item <= 8192:
        assert dlhs[0] == m


def _moe_operands(T, H, F, E, dtype, seed=0):
    rs = np.random.default_rng(seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rs.standard_normal(shape) * scale, dtype)

    return (draw(T, H), draw(H, E).astype(jnp.float32),
            draw(E, H, F, scale=H ** -0.5), draw(E, H, F, scale=H ** -0.5),
            draw(E, F, H, scale=F ** -0.5))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_moe_ffn_equal_between_the_two_lowerings(monkeypatch, dtype):
    """`moe_ffn`'s five outputs, and the gradients of Out, AuxLoss and
    ZLoss to its five inputs, through the kernels (interpreted) and
    through `lax.ragged_dot`: one body, `grouped_dot` the only
    difference."""
    T, H, F, E, k = 64, 128, 256, 8, 2          # 128 routed rows
    args = _moe_operands(T, H, F, E, dtype)

    def run():
        def fn(*a):
            return lm_ops.moe_ffn(*a, k)[:3]

        outs, vjp = jax.vjp(fn, *args)
        cots = (jnp.ones_like(outs[0]), jnp.ones((1,)), jnp.ones((1,)))
        return lm_ops.moe_ffn(*args, k), vjp(cots)

    calls = []
    real = grouped.tiles_for
    monkeypatch.setattr(grouped, "tiles_for",
                        lambda *a: calls.append(a) or real(*a))
    plain, plain_grads = run()
    assert calls == []                          # not a TPU place
    monkeypatch.setattr(grouped, "on_tpu", lambda: True)
    kern, kern_grads = run()
    assert calls and all(real(*a) is not None for a in calls)
    tol = 1e-5 if dtype == "float32" else 2e-2
    for a, b in zip(plain, kern):
        if a.dtype.kind == "i":
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=tol, atol=tol)
    for a, b in zip(plain_grads, kern_grads):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(a)), 1e-6)


def test_rows_no_tile_divides_fall_back_to_ragged_dot(monkeypatch):
    """On a TPU place, 100 tokens x top-2 = 200 rows: no row tile divides
    them, `grouped_dot` is `lax.ragged_dot` (no Pallas call in the trace)
    and the result is the plain one."""
    monkeypatch.setattr(grouped, "on_tpu", lambda: True)
    args = _moe_operands(100, 128, 256, 8, "float32")
    text = str(jax.make_jaxpr(lambda *a: lm_ops.moe_ffn(*a, 2))(*args))
    assert "pallas_call" not in text and "ragged_dot" in text
    taken = _moe_operands(64, 128, 256, 8, "float32")
    assert _kernel_calls(lambda *a: lm_ops.moe_ffn(*a, 2), *taken) == 3
    assert "ragged_dot" not in str(jax.make_jaxpr(
        lambda *a: lm_ops.moe_ffn(*a, 2))(*taken))


def _moe_program(tokens, hidden, width, experts=8, top_k=2):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[tokens, hidden],
                              dtype="float32", append_batch_size=False)
        y = fluid.layers.moe_ffn(x, experts, width, top_k)[0]
        fluid.optimizer.SGD(learning_rate=0.1).minimize(
            fluid.layers.mean(y))
    return main


@pytest.mark.parametrize("tokens,hidden,width,kernel", [
    (64, 128, 256, True),
    (-1, 128, 256, True),       # rows left open: taken to fit
    (100, 128, 256, False),     # 200 rows: no row tile divides them
    (64, 96, 256, False),       # K not a multiple of 128
    (64, 128, 200, False),      # M not a multiple of 128
])
def test_counter_follows_the_shapes(tokens, hidden, width, kernel):
    """`grouped_matmul_kernel` counts a `moe_ffn` op on a TPU place only,
    and only where the kernels take its shapes, `grouped_mlp_epilogues`
    with it (these widths fit VMEM); `moe_ffn_grouped` counts it
    everywhere."""
    prog = _moe_program(tokens, hidden, width)
    tpu, cpu = (SimpleNamespace(platform=p) for p in ("tpu", "cpu"))
    assert lm_ops.lowered_counts(prog, cpu) == {"moe_ffn_grouped": 1}
    want = {"moe_ffn_grouped": 1}
    if kernel:
        want["grouped_matmul_kernel"] = want["grouped_mlp_epilogues"] = 1
    assert lm_ops.lowered_counts(prog, tpu) == want


def test_backward_op_takes_the_forward_products():
    """The program's `moe_ffn_grad` op reads GateOut / UpOut / DownOut of
    its forward op, and a training step through the Executor gives the
    gradients the generic vjp gives."""
    prog = _moe_program(64, 128, 256)
    ops = {op.type: op for op in prog.global_block().ops}
    fwd, bwd = ops["moe_ffn"], ops["moe_ffn_grad"]
    for slot in ("GateOut", "UpOut", "DownOut"):
        assert bwd.input(slot) == fwd.output(slot) != []
    assert sorted(s for s in bwd.outputs) == [
        "Down@GRAD", "Gate@GRAD", "Router@GRAD", "Up@GRAD"]
    args = _moe_operands(64, 128, 256, 8, "float32", seed=3)
    mean = 1.0 / (64 * 128)
    ins = {s: [a] for s, a in zip(lm_ops._MOE_TRAINED, args)}
    x, router, *weights = args
    saved = lm_ops._moe_ffn(x, router, None, *weights,
                            lm_ops.Routing({"top_k": 2}, 8))[1]
    got = lm_ops.moe_ffn_grad_op(None, dict(
        ins, **{s: [p] for s, p in zip(lm_ops._MOE_PRODUCTS, saved)},
        **{"Out@GRAD": [jnp.full((64, 128), mean)]}), {"top_k": 2})
    want = jax.grad(lambda *a: jnp.mean(lm_ops.moe_ffn(*a, 2)[0]),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for slot, w in zip(lm_ops._MOE_TRAINED, want):
        np.testing.assert_allclose(got[slot + "@GRAD"][0], w, rtol=1e-5,
                                   atol=1e-7)
    # an op built without the saved products computes them itself
    again = lm_ops.moe_ffn_grad_op(None, dict(
        ins, **{"Out@GRAD": [jnp.full((64, 128), mean)]}), {"top_k": 2})
    for slot in lm_ops._MOE_TRAINED:
        np.testing.assert_allclose(again[slot + "@GRAD"][0],
                                   got[slot + "@GRAD"][0], rtol=1e-6,
                                   atol=1e-8)


def test_ragged_dot_is_the_reference_semantics():
    """What both lowerings compute, written out: group g's rows times
    rhs[g]."""
    counts = LAYOUTS["boundaries_inside_tiles"]
    rs = np.random.default_rng(1)
    lhs = rs.standard_normal((N, K)).astype(np.float32)
    rhs = rs.standard_normal((len(counts), K, M)).astype(np.float32)
    want = np.concatenate([
        lhs[lo:lo + c] @ rhs[g] for g, (lo, c) in enumerate(
            zip(np.cumsum(counts) - counts, counts))])
    got = grouped.grouped_dot(jnp.asarray(lhs), jnp.asarray(rhs),
                              jnp.asarray(counts, jnp.int32))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        lax.ragged_dot(jnp.asarray(lhs), jnp.asarray(rhs),
                       jnp.asarray(counts, jnp.int32)), want, rtol=1e-4,
        atol=1e-4)


PARTIAL = {
    "groups_end_inside_a_tile": [40, 0, 30, 27],
    "groups_end_on_a_tile": [64, 10, 54, 0],
    "one_row": [0, 1, 0, 0],
    "no_rows": [0, 0, 0, 0],
    "nearly_all": [100, 100, 50, 5],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", sorted(PARTIAL))
def test_groups_may_end_before_the_rows_do(layout, dtype):
    """`rows_past`: the groups hold the first R <= N rows (a layer that
    holds some of its experts sorts their rows first). The product and
    d lhs are `lax.ragged_dot`'s on those rows and ZERO past them,
    whatever the rows of lhs past them hold (here NaN: no visit computes
    with them), and d rhs sums the groups' rows alone (the rows past them
    hold other values than the reference's: in the groups' last tile they
    meet zeros)."""
    counts = jnp.asarray(PARTIAL[layout], jnp.int32)
    R = int(counts.sum())
    rs = np.random.default_rng(R)
    lhs, rhs, g = (jnp.asarray(rs.standard_normal(s), dtype) for s in (
        (N, K), (len(counts), K, M), (N, M)))
    tiles = (TM, (128, 128), (128, 128), (128, 128))
    out = grouped.grouped_matmul(lhs.at[R:].set(jnp.nan), rhs, counts, None,
                                 tiles, True)
    _, vjp = jax.vjp(lambda a, b: grouped.grouped_matmul(
        a, b, counts, None, tiles, True), lhs.at[R:].multiply(-3.0), rhs)
    d_lhs, d_rhs = vjp(g.at[R:].multiply(7.0))
    ref, ref_vjp = jax.vjp(lambda a, b: grouped.grouped_matmul(
        a, b, counts, None, None, True), lhs, rhs)
    ref_dl, ref_dr = ref_vjp(g)
    tol = 2e-6 if dtype == "float32" else 2 ** -8
    for ours, want in ((out, ref), (d_lhs, ref_dl), (d_rhs, ref_dr)):
        ours, want = (np.asarray(v, np.float32) for v in (ours, want))
        assert np.all(np.isfinite(ours))
        assert np.max(np.abs(ours - want)) <= tol * max(
            np.max(np.abs(want)), 1.0)
    assert not np.asarray(out, np.float32)[R:].any()
    assert not np.asarray(d_lhs, np.float32)[R:].any()


@pytest.mark.parametrize("first,held", [(0, 2), (2, 2), (5, 3), (0, 8)])
def test_moe_ffn_holding_a_share_equals_the_dense_sum(monkeypatch, first,
                                                       held):
    """`moe_ffn` told to hold experts [first, +held) of 8, sigmoid scores,
    a bias in the choice, renormalised and scaled weights, through the
    kernels (interpreted) and through `lax.ragged_dot`: both are the dense
    sum over the held experts of w_te E_e(x_t); the counts are over all 8
    experts and the rows held are the held experts' counts."""
    T, H, F, E, k = 64, 128, 128, 8, 2
    x, router, gate, up, down = _moe_operands(T, H, F, E, "float32", seed=3)
    bias = jnp.asarray(np.random.default_rng(5).normal(0, 0.2, E),
                       jnp.float32)
    attrs = dict(top_k=k, score_func="sigmoid", norm_topk=True,
                 routed_scale=2.0, first_expert=first, held_experts=held)
    sl = slice(first, first + held)
    routing = lm_ops.Routing(attrs, E)
    results = []
    for on_chip in (True, False):
        monkeypatch.setattr(grouped, "on_tpu", lambda on_chip=on_chip: on_chip)
        (o, _, _, ids, counts, rows), _ = lm_ops._moe_ffn(
            x, router, bias, gate[sl], up[sl], down[sl], routing)
        results.append(np.asarray(o))
    scores = jax.nn.sigmoid(x @ router)
    _, top = lax.top_k(scores + bias, k)
    w = jnp.take_along_axis(scores, top, 1)
    w = w / w.sum(1, keepdims=True) * 2.0
    dense = jnp.zeros((T, H))
    for e in range(first, first + held):
        y = (jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e]
        dense += y * jnp.sum(jnp.where(top == e, w, 0.0), 1, keepdims=True)
    for got in results:
        assert np.max(np.abs(got - dense)) <= 2e-5 * np.max(np.abs(dense))
    np.testing.assert_array_equal(np.sort(ids, 1), np.sort(top, 1))
    assert int(counts.sum()) == T * k
    assert int(rows[0]) == int(counts[sl].sum())


# ------------------------------------------------------------ row bound
@pytest.mark.parametrize("n_rows,held,n_experts,want", [
    (65536, 32, 256, 16384),    # the Laguna cell: top-8 of 8192 tokens
    (16384, 8, 64, 4096),       # the Xing cell: top-4 of 4096 tokens
    (65536, 64, 64, 65536),     # OLMoE: every expert held, no bound
    (128, 2, 8, 128),           # a tile is longer than the rows
    (4096, 3, 8, 3072),         # twice three eighths
    (4096, 5, 8, 4096),         # twice the share passes the rows
    (5000, 1, 16, 1024),        # 625 rows: up to two tiles
])
def test_row_bound_is_a_function_of_the_shapes(n_rows, held, n_experts,
                                               want):
    """Twice the even-load share, rounded up to the kernels' longest row
    tile, never above the rows, the rows themselves where all are held."""
    got = lm_ops.row_bound(n_rows, held, n_experts)
    assert got == want
    assert got <= n_rows
    assert got == n_rows or got % grouped.ROW_TILES[0] == 0
    assert got >= min(n_rows, 2 * n_rows * held // n_experts)
    if got < n_rows:
        assert grouped.takes(got, 128, 128)


BOUND_T, BOUND_K, BOUND_E = 64, 2, 8            # 128 choice rows


BOUND_DIMS = (BOUND_T, BOUND_K, BOUND_E, 128, 128)     # T, k, E, H, F


def _routed_so_that(held_rows, first, held, score_func, seed,
                    dims=BOUND_DIMS):
    """Operands of a share-holding `moe_ffn` whose router sends exactly
    `held_rows` of the T * k choices to the held experts: the logits are
    chosen and the router solved from them (64 tokens of width 128 unless
    `dims` says otherwise)."""
    T, k, E, H, F = dims
    x, _, gate, up, down = _moe_operands(T, H, F, E, "float32", seed=seed)
    rs = np.random.default_rng(seed)
    inside = list(range(first, first + held))
    outside = [e for e in range(E) if e not in inside]
    logits = rs.normal(-4.0, 0.3, (T, E))
    for slot in range(T * k):
        t, j = divmod(slot, k)
        pool = inside if slot < held_rows else outside
        logits[t, pool[(t + j) % len(pool)]] = 4.0 + 0.5 * j
    router = np.linalg.lstsq(np.asarray(x, np.float64), logits,
                             rcond=None)[0]
    bias = jnp.asarray(rs.normal(0, 0.05, E), jnp.float32)
    sl = slice(first, first + held)
    attrs = dict(top_k=k, score_func=score_func, first_expert=first,
                 held_experts=held)
    if score_func == "sigmoid":
        attrs.update(norm_topk=True, routed_scale=2.0)
    ins = {"X": [x], "Router": [jnp.asarray(router, jnp.float32)],
           "Bias": [bias], "Gate": [gate[sl]], "Up": [up[sl]],
           "Down": [down[sl]]}
    return ins, attrs


def _step_of(ins, attrs, seed):
    """Forward op, then the backward op on its saved products."""
    fwd = lm_ops.moe_ffn_op(None, ins, attrs)
    rs = np.random.default_rng(seed + 100)
    cots = {"Out@GRAD": [jnp.asarray(rs.standard_normal(
        fwd["Out"][0].shape), jnp.float32)],
        "AuxLoss@GRAD": [jnp.full((1,), 0.7)],
        "ZLoss@GRAD": [jnp.full((1,), 0.3)]}
    bwd = lm_ops.moe_ffn_grad_op(None, dict(
        ins, **{s: fwd[s] for s in lm_ops._MOE_PRODUCTS}, **cots), attrs)
    return fwd, bwd, cots


def _dense_loss(x, router, gate, up, down, bias, attrs, cots,
                dims=BOUND_DIMS):
    """sum(Out * d Out) + 0.7 AuxLoss + 0.3 ZLoss with Out as the dense
    sum over the held experts: what the op's gradients are gradients of."""
    T, k, E = dims[:3]
    first, held = attrs["first_expert"], attrs["held_experts"]
    logits = jnp.dot(x, router, precision=lax.Precision.HIGHEST)
    lse = jax.nn.logsumexp(logits, axis=-1)
    scores = (jax.nn.sigmoid(logits) if attrs["score_func"] == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    _, top = lax.top_k(scores + bias, k)
    w = jnp.take_along_axis(scores, top, 1)
    if attrs.get("norm_topk"):
        w = w / (w.sum(1, keepdims=True) + 1e-20) * attrs["routed_scale"]
    o = jnp.zeros_like(x)
    for e in range(held):
        y = jnp.dot(jax.nn.silu(jnp.dot(x, gate[e], precision="highest"))
                    * jnp.dot(x, up[e], precision="highest"), down[e],
                    precision="highest")
        o += y * jnp.sum(jnp.where(top == first + e, w, 0.0), 1,
                         keepdims=True)
    share = jnp.sum(top.reshape(-1)[:, None] == jnp.arange(E)[None, :],
                    axis=0) / (T * k)
    aux = E * jnp.sum(lax.stop_gradient(share) * jnp.mean(scores, axis=0))
    return (jnp.sum(o * cots["Out@GRAD"][0]) + 0.7 * aux
            + 0.3 * jnp.mean(jnp.square(lse)))


# (rows the held experts receive, named against the bound B of the case)
BOUND_ROWS = {"none": lambda B: 0, "under": lambda B: B - 23,
              "at": lambda B: B, "one_over": lambda B: B + 1,
              "every_row": lambda B: BOUND_T * BOUND_K}


# through `lax.ragged_dot` every case; through the kernels, interpreted,
# one case of each row count
BOUND_CASES = [(first, held, score_func, rows_case, False)
               for first, held in [(0, 2), (2, 2), (5, 3)]
               for score_func in ("sigmoid", "softmax")
               for rows_case in sorted(BOUND_ROWS)] + [
    (2, 2, "sigmoid", rows_case, True) for rows_case in sorted(BOUND_ROWS)]


@pytest.mark.parametrize(
    "first,held,score_func,rows_case,kernels", BOUND_CASES,
    ids=["-".join([f"{c[0]}+{c[1]}", c[2], c[3],
                   "kernels" if c[4] else "ragged_dot"])
         for c in BOUND_CASES])
def test_a_row_bound_gives_the_full_size_path(monkeypatch, first, held,
                                              score_func, rows_case,
                                              kernels):
    """`moe_ffn` + `moe_ffn_grad` of a layer that holds a share of its 8
    experts, with a row bound B below its 128 choice rows (row tile 32: B
    = 64 for 2 held, 96 for 3), against the same ops with no bound, for
    RowsHeld = 0, under B, B, B + 1 (the overflow branch) and all 128 (a
    router that sends every choice to held experts): every output, the
    five gradients, and `DownOut`'s non-zero rows = RowsHeld in each. The
    full-size path's own gradients are held to the dense sum's."""
    monkeypatch.setattr(grouped, "ROW_TILES", (32,))
    monkeypatch.setattr(grouped, "on_tpu", lambda: kernels)
    N = BOUND_T * BOUND_K
    B = lm_ops.row_bound(N, held, BOUND_E)
    assert B == {2: 64, 3: 96}[held]
    R = BOUND_ROWS[rows_case](B)
    seed = 7 + first
    ins, attrs = _routed_so_that(R, first, held, score_func, seed)
    fwd, bwd, cots = _step_of(ins, attrs, seed)
    assert int(fwd["RowsHeld"][0][0]) == R
    assert fwd["GateOut"][0].shape == fwd["UpOut"][0].shape == (B, 128)
    down_out = np.asarray(fwd["DownOut"][0])
    assert down_out.shape == (N, 128)
    assert int(np.any(down_out != 0, axis=1).sum()) == R
    assert not down_out[R:].any()

    monkeypatch.setattr(lm_ops, "row_bound", lambda n, *_: n)
    full, full_bwd, _ = _step_of(ins, attrs, seed)
    assert full["GateOut"][0].shape == (N, 128)
    np.testing.assert_array_equal(full["DownOut"][0], down_out)
    for slot in ("ExpertIds", "TokensPerExpert", "RowsHeld", "AuxLoss",
                 "ZLoss"):
        # the same sums in the same order: bit for bit
        np.testing.assert_array_equal(fwd[slot][0], full[slot][0])
    # Out and the gradients: the k choices of a token are summed a choice
    # at a time from a bounded table, in one reduction from the full one
    got, want = np.asarray(fwd["Out"][0]), np.asarray(full["Out"][0])
    assert np.max(np.abs(got - want)) <= 1e-6 * np.max(np.abs(want))
    for slot in lm_ops._MOE_TRAINED:
        got, want = (np.asarray(g[slot + "@GRAD"][0])
                     for g in (bwd, full_bwd))
        assert np.max(np.abs(got - want)) <= 1e-6 * np.max(np.abs(want))

    args = [ins[s][0] for s in lm_ops._MOE_TRAINED]
    dense = jax.grad(_dense_loss, argnums=(0, 1, 2, 3, 4))(
        *args, ins["Bias"][0], attrs, cots)
    for slot, want in zip(lm_ops._MOE_TRAINED, dense):
        got = np.asarray(full_bwd[slot + "@GRAD"][0])
        assert np.max(np.abs(got - want)) <= 2e-4 * max(
            np.max(np.abs(want)), 1e-6), slot


# (cell, top_k, experts, held, H): the four share-holding cells' expert
# layers with the experts cut so that held / experts stays the cell's (the
# row bound is the cell's share of the choice rows: a half, a quarter, a
# half, a quarter = the tokens themselves), 64 tokens, experts of 128
BY_TOKEN_SHAPES = [("smallthinker_21b_a3b", 6, 24, 6, 2560),
                   ("laguna_xs_2", 8, 64, 8, 2048),
                   ("lfm2_8b_a1b", 4, 16, 4, 2048),
                   ("xing4_0_29b_a4b", 4, 32, 4, 3584)]


@pytest.mark.parametrize("rows_case", ["under", "one_over"])
@pytest.mark.parametrize("cell,k,E,held,H", BY_TOKEN_SHAPES,
                         ids=[c[0] for c in BY_TOKEN_SHAPES])
def test_bounded_rows_summed_by_token_give_the_layer_s_gradients(
        monkeypatch, cell, k, E, held, H, rows_case):
    """`moe_ffn` + `moe_ffn_grad` with the bounded sums taken by token
    through the row-tile kernel (PR 40: the place steered to a TPU for
    `row_sum` alone, the kernel interpreted), at each share-holding
    cell's top_k, held share and row width, in a step whose held experts'
    rows fit the bound (the kernel form runs) and in one where they do
    not (the `cond`'s other branch: the full table, no kernel): every
    output and gradient against the same ops with the k gathers, and the
    gradients against `jax.grad` of the plain dense sum."""
    from paddle_tpu.parallel import row_sum

    T, F, first = 64, 128, 1
    dims = (T, k, E, H, F)
    monkeypatch.setattr(grouped, "ROW_TILES", (32,))
    N = T * k
    B = lm_ops.row_bound(N, held, E)
    assert B < N and B == 2 * N * held // E
    R = {"under": B - 23, "one_over": B + 1}[rows_case]
    ins, attrs = _routed_so_that(R, first, held, "sigmoid", 11, dims)
    want_fwd, want_bwd, cots = _step_of(ins, attrs, 11)
    assert int(want_fwd["RowsHeld"][0][0]) == R

    calls = []
    real = row_sum.sum_sorted_rows

    def interpreted(token, rows, V, tiles, interpret=None, **kw):
        calls.append((rows.shape, V, "weights" in kw and
                      kw["weights"] is not None))
        return real(token, rows, V, tiles, interpret=True, **kw)

    monkeypatch.setattr(row_sum, "pallas_interpret", lambda: False)
    monkeypatch.setattr(row_sum, "sum_sorted_rows", interpreted)
    monkeypatch.setattr(row_sum, "tiles_for", lambda H: (32, 16))
    monkeypatch.setattr(row_sum, "takes_choices",
                        lambda T, k, H, rows, dtype: rows < T * k)
    fwd, bwd, _ = _step_of(ins, attrs, 11)
    # traced in both branches of both ops' `cond`s: the combine in the
    # forward op and in the backward op's vjp, and the dispatch's backward
    assert set(calls) == {((B + 16, H), T, True), ((B + 16, H), T, False)}
    for slot in ("ExpertIds", "TokensPerExpert", "RowsHeld", "AuxLoss",
                 "ZLoss", *lm_ops._MOE_PRODUCTS):
        np.testing.assert_array_equal(fwd[slot][0], want_fwd[slot][0])
    got, want = np.asarray(fwd["Out"][0]), np.asarray(want_fwd["Out"][0])
    assert np.max(np.abs(got - want)) <= 1e-6 * np.max(np.abs(want))
    if rows_case == "one_over":
        # the overflow branch is the parent's: to the bit
        np.testing.assert_array_equal(got, want)
    for slot in lm_ops._MOE_TRAINED:
        got, want = (np.asarray(g[slot + "@GRAD"][0])
                     for g in (bwd, want_bwd))
        assert np.max(np.abs(got - want)) <= 1e-6 * np.max(np.abs(want))
    args = [ins[s][0] for s in lm_ops._MOE_TRAINED]
    dense = jax.grad(_dense_loss, argnums=(0, 1, 2, 3, 4))(
        *args, ins["Bias"][0], attrs, cots, dims)
    for slot, want in zip(lm_ops._MOE_TRAINED, dense):
        got = np.asarray(bwd[slot + "@GRAD"][0])
        assert np.max(np.abs(got - want)) <= 2e-4 * max(
            np.max(np.abs(want)), 1e-6), slot


def test_a_layer_that_holds_every_expert_has_no_bound_and_no_cond():
    """OLMoE's layer: no `cond` in the forward or the backward op; a
    share-holding layer with a bound below its rows has one in each."""
    args = _moe_operands(64, 128, 128, 8, "float32")
    ins = {s: [a] for s, a in zip(lm_ops._MOE_TRAINED, args)}

    def both(ins, attrs):
        fwd = jax.make_jaxpr(lambda: lm_ops.moe_ffn_op(None, ins, attrs))()
        bwd = jax.make_jaxpr(lambda: lm_ops.moe_ffn_grad_op(None, dict(
            ins, **{"Out@GRAD": [jnp.ones((64, 128))]}), attrs))()
        return str(fwd), str(bwd)

    for text in both(ins, {"top_k": 2}):
        assert " cond[" not in text
    share = dict(ins, Gate=[args[2][:1]], Up=[args[3][:1]],
                 Down=[args[4][:1]])
    attrs = {"top_k": 2, "first_expert": 3, "held_experts": 1}
    for text in both(share, attrs):     # 128 rows: one tile of 512 covers
        assert " cond[" not in text
    import unittest.mock
    with unittest.mock.patch.object(grouped, "ROW_TILES", (32,)):
        for text in both(share, attrs):
            assert text.count(" cond[") == 1


# ------------------------------------------------------------ grouped_mlp
H, F = 256, 256
MLP_LAYOUTS = dict(LAYOUTS, **{"past_" + k: v for k, v in PARTIAL.items()})
MLP_OUTPUTS = ["ys", "d_xs", "d_gate", "d_up", "d_down"]


def _mlp_operands(n_groups, dtype, seed=0):
    rs = np.random.default_rng(seed)
    return [jnp.asarray(rs.standard_normal(shape) * scale, dtype)
            for shape, scale in (
                ((N, H), 1.0), ((n_groups, H, F), H ** -0.5),
                ((n_groups, H, F), H ** -0.5), ((n_groups, F, H), F ** -0.5),
                ((N, H), 1.0))]


def _composition(xs, gate, up, down, counts, rows_past):
    """What `grouped_mlp` replaces, -> (ys, a, b): three `grouped_dot`s
    (`lax.ragged_dot` here) and `jax.nn.silu`, joined by autodiff."""
    a = grouped.grouped_dot(xs, gate, counts, None, rows_past)
    b = grouped.grouped_dot(xs, up, counts, None, rows_past)
    return grouped.grouped_dot(jax.nn.silu(a) * b, down, counts, None,
                               rows_past), a, b


@functools.lru_cache(maxsize=None)
def _mlp_both(layout, dtype, whole_k):
    """(kernels, composition): each (ys, d xs, d gate, d up, d down) as
    float32 numpy. `whole_k`: every contraction in one tile (the product
    goes straight to the epilogue), else in two (through float32 sums in
    scratch)."""
    counts = jnp.asarray(MLP_LAYOUTS[layout], jnp.int32)
    past = layout.startswith("past_")
    xs, gate, up, down, g = _mlp_operands(len(counts), dtype, len(layout))
    t = (256, 256) if whole_k else (128, 128)
    tiles = ((TM, t, t, t),) * 2
    results = []
    for fn in (lambda *w: grouped._mlp(*w, counts, None, tiles, past)[0],
               lambda *w: _composition(*w, counts, past)[0]):
        out, vjp = jax.vjp(fn, xs, gate, up, down)
        results.append([np.asarray(x, np.float32) for x in (out, *vjp(g))])
    return results


# every layout in float32 with whole contractions; bf16, and contractions
# split in two, over one layout of each kind (nine interpreted kernels a
# case cost the CPU ~9 s)
_OF_EACH_KIND = ["boundaries_inside_tiles", "empty_experts_first_middle_last",
                 "one_expert_owns_every_row", "past_groups_end_inside_a_tile"]
MLP_CASES = ([(layout, "float32", True) for layout in sorted(MLP_LAYOUTS)]
             + [(layout, "bfloat16", True) for layout in _OF_EACH_KIND]
             + [(layout, "float32", False) for layout in _OF_EACH_KIND[:3]]
             + [(_OF_EACH_KIND[3], "bfloat16", False)])


@pytest.mark.parametrize("which", MLP_OUTPUTS)
@pytest.mark.parametrize(
    "layout,dtype,whole_k", MLP_CASES,
    ids=["-".join((c[0], c[1], "whole_k" if c[2] else "split_k"))
         for c in MLP_CASES])
def test_mlp_matches_the_composition(layout, dtype, whole_k, which):
    """`grouped_mlp`'s nine kernels with their epilogues (SiLU * up behind
    the up product, its backward behind d h, the second d xs product added
    onto the first, h formed in front of d down) give ys and all four
    gradients of three `grouped_dot`s + `jax.nn.silu`: to float32 rounding
    in float32; in bf16 to the rounding of a chain of three bf16 products
    (h is rounded once from the float32 sums here, from a rounded b
    there). With groups that end before the rows, ys and d xs are zero
    past them."""
    i = MLP_OUTPUTS.index(which)
    ours, ref = (r[i] for r in _mlp_both(layout, dtype, whole_k))
    assert ours.shape == ref.shape and np.all(np.isfinite(ours))
    tol = 1e-5 if dtype == "float32" else 2e-2
    assert np.max(np.abs(ours - ref)) <= tol * max(np.max(np.abs(ref)), 1e-3)
    if layout.startswith("past_") and which in ("ys", "d_xs"):
        assert not ours[sum(MLP_LAYOUTS[layout]):].any()


@pytest.mark.parametrize("layout,dtype", [
    (layout, "float32") for layout in sorted(PARTIAL)]
    + [("groups_end_inside_a_tile", "bfloat16")])
def test_mlp_reads_no_row_past_the_groups(layout, dtype):
    """With `rows_past` the saved a and b hold anything past the groups'
    rows (no kernel wrote them; here NaN), and so do h, d a and d b made
    from them: every gradient is finite and the one the composition gives
    from zeros there."""
    counts = jnp.asarray(PARTIAL[layout], jnp.int32)
    R = int(counts.sum())
    xs, gate, up, down, g = _mlp_operands(len(counts), dtype, R)
    tiles = ((TM, (128, 128), (128, 128), (128, 128)),) * 2
    ys, a, b = grouped._mlp(xs, gate, up, down, counts, None, tiles, True)
    saved = (a.at[R:].set(jnp.nan), b.at[R:].set(jnp.nan), ys)
    got = jax.vjp(lambda *w: grouped._mlp(
        *w, counts, saved, tiles, True)[0], xs, gate, up, down)[1](g)
    want = jax.vjp(lambda *w: _composition(*w, counts, True)[0],
                   xs, gate, up, down)[1](g)
    tol = 1e-5 if dtype == "float32" else 2e-2
    for ours, ref in zip(got, want):
        ours, ref = (np.asarray(v, np.float32) for v in (ours, ref))
        assert np.all(np.isfinite(ours))
        assert np.max(np.abs(ours - ref)) <= tol * max(np.max(np.abs(ref)),
                                                       1e-3)
    assert not np.asarray(got[0], np.float32)[R:].any()


@pytest.mark.parametrize("tk", [128, 256], ids=["split_k", "whole_k"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_product_is_added_onto_an_existing_one(layout, dtype, tk):
    """`_gmm(..., "add", (existing,))`: the tile stored is existing +
    product, from the float32 sums, over `existing` in place, in tiles a
    boundary crosses too: the sum of two d lhs products with no pass to
    add them."""
    counts = jnp.asarray(LAYOUTS[layout], jnp.int32)
    rs = np.random.default_rng(len(layout))
    g1, g2, w1, w2 = (jnp.asarray(rs.standard_normal(s), dtype) for s in (
        (N, F), (N, F), (len(counts), H, F), (len(counts), H, F)))
    meta, n = grouped.visits(counts, N, TM, False)
    first = grouped._gmm(g1, w1, meta, n, (TM, tk, 128), True)
    both = grouped._gmm(g2, w2, meta, n, (TM, tk, 128), True, "add",
                        (first,))
    want = sum(np.asarray(lax.ragged_dot(
        g, jnp.swapaxes(w, 1, 2), counts,
        preferred_element_type=jnp.float32), np.float32)
        for g, w in ((g1, w1), (g2, w2)))
    tol = 2e-6 if dtype == "float32" else 2 ** -7   # two roundings in bf16
    assert np.max(np.abs(np.asarray(both, np.float32) - want)) \
        <= tol * np.max(np.abs(want))


@pytest.mark.parametrize("n_rows,h,f,products,whole_mlp", [
    (65536, 2048, 1024, True, True),        # the olmoe_1b_7b cell
    (16384, 3584, 1024, True, True),        # the xing4_0_29b_a4b cell
    (None, 128, 256, True, True),
    (65536, 4096, 4096, True, False),       # d h alone asks 144 MiB of VMEM
    (65536, 2048 + 64, 1024, False, False),
    (65536 + 64, 2048, 1024, False, False),
])
def test_mlp_kernels_are_taken_where_they_fit(monkeypatch, n_rows, h, f,
                                              products, whole_mlp):
    """`mlp_takes`: the shapes `takes` accepts, less those whose widest
    kernel with its side tiles passes the VMEM limit; there `grouped_mlp`
    is the composition of three `grouped_dot`s it was before."""
    assert grouped.takes(n_rows, h, f) == products
    assert grouped.mlp_takes(n_rows, h, f) == whole_mlp
    if n_rows is not None:
        return
    monkeypatch.setattr(grouped, "on_tpu", lambda: True)
    monkeypatch.setattr(grouped, "ROW_TILES", (TM,))
    counts = jnp.asarray(LAYOUTS["even"], jnp.int32)
    w = _mlp_operands(4, "float32")[:4]

    def lowered(fn):
        return str(jax.make_jaxpr(fn)(*w))

    taken = lowered(lambda *w: grouped.grouped_mlp(*w, counts))
    monkeypatch.setattr(grouped, "_VMEM_LIMIT", 2 ** 20)
    refused = lowered(lambda *w: grouped.grouped_mlp(*w, counts))
    assert refused == lowered(
        lambda *w: _composition(*w, counts, False)) != taken


# ------------------------------------------- experts of width 512, 32 held
# 32 groups over 512 rows, walked in tiles of 64: most tiles hold several
# groups, five groups are empty (first, last, two in a row), one fills a
# tile exactly, and the list ends 50 rows before the rows do
SMALL_GROUPS = [0, 9, 23, 64, 5, 17, 0, 0, 31, 12, 7, 60, 3, 26, 11, 19,
                8, 22, 1, 30, 14, 6, 27, 10, 4, 16, 13, 2, 18, 2, 2, 0]
WIDTH = 512


@functools.lru_cache(maxsize=None)
def _mlp_of_small_groups(past):
    counts = np.asarray(SMALL_GROUPS, np.int32)
    assert len(counts) == 32 and counts.sum() == 512 - 50
    if not past:
        counts[3] += 50
    counts = jnp.asarray(counts)
    rs = np.random.default_rng(32)
    n = int(counts.shape[0])
    xs, gate, up, down, g = (
        jnp.asarray(rs.standard_normal(shape) * scale, jnp.float32)
        for shape, scale in (((512, WIDTH), 1.0), ((n, WIDTH, WIDTH), .04),
                             ((n, WIDTH, WIDTH), .04),
                             ((n, WIDTH, WIDTH), .04), ((512, WIDTH), 1.0)))
    t = (WIDTH, WIDTH)
    tiles = ((TM, t, t, t),) * 2
    results = []
    for fn in (lambda *w: grouped._mlp(*w, counts, None, tiles, past)[0],
               lambda *w: _composition(*w, counts, past)[0]):
        out, vjp = jax.vjp(fn, xs, gate, up, down)
        results.append([np.asarray(x, np.float32) for x in (out, *vjp(g))])
    return results


@pytest.mark.parametrize("which", MLP_OUTPUTS)
@pytest.mark.parametrize("past", [False, True],
                         ids=["every_row_grouped", "a_partial_group_list"])
def test_mlp_at_width_512_over_32_small_groups(past, which):
    """`grouped_mlp`'s kernels at N = 512 (gate, up) and K = 512 (down)
    with whole contractions, over 32 groups of 0 to 64 rows: many
    boundaries a tile, empty groups, and with `past` a group list that
    ends before the rows do."""
    i = MLP_OUTPUTS.index(which)
    ours, ref = (r[i] for r in _mlp_of_small_groups(past))
    assert ours.shape == ref.shape and np.all(np.isfinite(ours))
    assert np.max(np.abs(ours - ref)) <= 1e-5 * max(np.max(np.abs(ref)),
                                                     1e-3)
    if past and which in ("ys", "d_xs"):
        assert not ours[512 - 50:].any()
    if which in ("d_gate", "d_up", "d_down"):
        empty = [e for e, c in enumerate(SMALL_GROUPS) if c == 0]
        assert not ours[empty].any() and ours[1].any()


def test_width_512_takes_the_kernels_with_their_epilogues():
    """[65536, 2048] x [32, 2048, 512] and back, the `laguna_xs_2` cell's
    grouped products: whole contractions, the longest row tile, and the
    two widest kernels inside the VMEM limit."""
    assert grouped.mlp_takes(65536, 2048, 512)
    for dtype in ("bfloat16", "float32"):
        assert grouped.tiles_for(65536, 2048, 512, dtype) == (
            512, (2048, 512), (512, 2048), (2048, 512))
    assert grouped.tiles_for(65536, 512, 2048, "bfloat16") == (
        512, (512, 2048), (2048, 512), (512, 2048))


# ------------------------------------------------- experts gated by ReLU
def _relu_composition(xs, gate, up, down, counts, rows_past):
    a = grouped.grouped_dot(xs, gate, counts, None, rows_past)
    b = grouped.grouped_dot(xs, up, counts, None, rows_past)
    return grouped.grouped_dot(jnp.maximum(a, 0) * b, down, counts, None,
                               rows_past), a, b


@functools.lru_cache(maxsize=None)
def _relu_mlp_both(layout, dtype, whole_k):
    """`_mlp_both` with `activation="relu"`: the `relu_mul` /
    `relu_mul_grad` epilogues and the relu(a) * b tile in front of d
    down, interpreted, against three `grouped_dot`s and `jnp.maximum`."""
    counts = jnp.asarray(MLP_LAYOUTS[layout], jnp.int32)
    past = layout.startswith("past_")
    xs, gate, up, down, g = _mlp_operands(len(counts), dtype, len(layout))
    t = (256, 256) if whole_k else (128, 128)
    tiles = ((TM, t, t, t),) * 2
    results = []
    for fn in (lambda *w: grouped._mlp(*w, counts, None, tiles, past,
                                       "relu")[0],
               lambda *w: _relu_composition(*w, counts, past)[0]):
        out, vjp = jax.vjp(fn, xs, gate, up, down)
        results.append([np.asarray(x, np.float32) for x in (out, *vjp(g))])
    return results


RELU_CASES = [("empty_experts_first_middle_last", "float32", True),
              ("boundaries_inside_tiles", "float32", False),
              ("past_groups_end_inside_a_tile", "float32", True),
              ("empty_experts_first_middle_last", "bfloat16", True)]


@pytest.mark.parametrize("which", MLP_OUTPUTS)
@pytest.mark.parametrize(
    "layout,dtype,whole_k", RELU_CASES,
    ids=["-".join((c[0], c[1], "whole_k" if c[2] else "split_k"))
         for c in RELU_CASES])
def test_relu_mlp_matches_the_composition(layout, dtype, whole_k, which):
    """ReLU-gated experts through the same nine kernels: ys and all four
    gradients of three `grouped_dot`s + relu, at ragged group lists with
    empty groups, with boundaries inside tiles and with groups that end
    before the rows do; and it is another function than SiLU's."""
    i = MLP_OUTPUTS.index(which)
    ours, ref = (r[i] for r in _relu_mlp_both(layout, dtype, whole_k))
    assert ours.shape == ref.shape and np.all(np.isfinite(ours))
    tol = 1e-5 if dtype == "float32" else 2e-2
    assert np.max(np.abs(ours - ref)) <= tol * max(np.max(np.abs(ref)), 1e-3)
    if layout.startswith("past_") and which in ("ys", "d_xs"):
        assert not ours[sum(MLP_LAYOUTS[layout]):].any()
    if layout in MLP_LAYOUTS and (layout, dtype, whole_k) in MLP_CASES:
        silu = _mlp_both(layout, dtype, whole_k)[0][i]
        assert np.max(np.abs(silu - ours)) > 1e-2 * np.max(np.abs(ours))


@pytest.mark.parametrize("which", MLP_OUTPUTS)
def test_relu_mlp_at_k_2560_f_768_tiles(which):
    """The `smallthinker_21b_a3b` cell's widths with the tiles `tiles_for`
    gives them in bf16 (K 2560 and F 768 each whole in one tile), over a
    ragged group list with an empty group, `rows_past` as the cell has it
    (16 of 64 experts held)."""
    i = MLP_OUTPUTS.index(which)
    ours, ref = (r[i] for r in _relu_at_the_cell_s_widths())
    assert ours.shape == ref.shape and np.all(np.isfinite(ours))
    assert np.max(np.abs(ours - ref)) <= 2e-2 * max(np.max(np.abs(ref)), 1e-3)
    if which in ("ys", "d_xs"):
        assert not ours[200:].any() and ours[:200].any()
    if which in ("d_gate", "d_up", "d_down"):
        assert not ours[1].any() and ours[0].any()


@functools.lru_cache(maxsize=None)
def _relu_at_the_cell_s_widths():
    h, f, n = 2560, 768, 256
    counts = jnp.asarray([90, 0, 110], jnp.int32)     # 200 of the 256 rows
    rs = np.random.default_rng(7)
    xs, gate, up, down, g = (
        jnp.asarray(rs.standard_normal(shape) * scale, jnp.bfloat16)
        for shape, scale in (((n, h), 1.0), ((3, h, f), h ** -0.5),
                             ((3, h, f), h ** -0.5), ((3, f, h), f ** -0.5),
                             ((n, h), 1.0)))
    tiles = (grouped.tiles_for(n, h, f, "bfloat16"),
             grouped.tiles_for(n, f, h, "bfloat16"))
    assert tiles[0] == (256, (2560, 768), (768, 2560), (2560, 768))
    assert tiles[1] == (256, (768, 2560), (2560, 768), (768, 2560))
    results = []
    for fn in (lambda *w: grouped._mlp(*w, counts, None, tiles, True,
                                       "relu")[0],
               lambda *w: _relu_composition(*w, counts, True)[0]):
        out, vjp = jax.vjp(fn, xs, gate, up, down)
        results.append([np.asarray(x, np.float32) for x in (out, *vjp(g))])
    return results


def test_k_2560_f_768_takes_the_kernels_with_their_epilogues():
    """[24576, 2560] x [16, 2560, 768] and back (the row bound's rows of
    the `smallthinker_21b_a3b` cell) and the overflow's [49152, .]: whole
    contractions in bf16, the longest row tile, inside the VMEM limit."""
    for rows in (24576, 49152):
        assert grouped.mlp_takes(rows, 2560, 768)
        assert grouped.tiles_for(rows, 2560, 768, "bfloat16") == (
            512, (2560, 768), (768, 2560), (2560, 768))
        assert grouped.tiles_for(rows, 768, 2560, "bfloat16") == (
            512, (768, 2560), (2560, 768), (768, 2560))
    assert max(grouped._vmem_bytes(512, 2560, 768, 2, 2, 2),
               grouped._vmem_bytes(512, 768, 2560, 2, 1, 1)) \
        < grouped._VMEM_LIMIT // 2


def test_relu_epilogues_hold_no_transcendental():
    """What changes in the kernels' cost estimate: a ReLU gate has no
    exponential. (That the epilogue's name stands on the kernels' op_name
    is held where op_names exist: tests/test_tpu_compile.py.)"""
    counts = jnp.asarray([100, 0, 156], jnp.int32)
    xs, gate, up, down, _ = _mlp_operands(3, "float32")
    tiles = ((TM, (256, 256), (256, 256), (256, 256)),) * 2

    def text(activation):
        return str(jax.make_jaxpr(lambda *w: jax.vjp(
            lambda *v: grouped._mlp(*v, counts, None, tiles, False,
                                    activation)[0], *w)[1](w[0]))(
                xs, gate, up, down))

    relu, silu = text("relu"), text("silu")
    assert "logistic" not in relu and "logistic" in silu


@pytest.mark.parametrize("model", ["olmoe", "xing4", "laguna"])
def test_defaults_lower_to_the_text_they_lowered_to(model):
    """`moe_ffn` as each accepted model builds it (no `router_input`, no
    `activation`): the op has no `RouterInput` slot and no `activation`
    attribute, and its lowering, forward and backward, is text for text
    that of the op given `activation="silu"` outright and that of a
    router told to read X itself: nothing of the new paths is traced."""
    from paddle_tpu.ops import lm_ops

    attrs = {"olmoe": {"top_k": 2},
             "xing4": {"top_k": 2, "score_func": "sigmoid",
                       "norm_topk": True, "routed_scale": 2.5,
                       "first_expert": 2, "held_experts": 2},
             "laguna": {"top_k": 2, "score_func": "sigmoid",
                        "norm_topk": True, "routed_scale": 2.5,
                        "first_expert": 4, "held_experts": 4}}[model]
    x, router, gate, up, down = _moe_operands(64, 32, 16, 8, "float32")
    held = attrs.get("held_experts", 8)
    ins = {"X": [x], "Router": [router], "Gate": [gate[:held]],
           "Up": [up[:held]], "Down": [down[:held]]}
    if model != "olmoe":
        ins["Bias"] = [jnp.linspace(-0.1, 0.1, 8)]

    def text(ins, attrs):
        def both(x, router, gate, up, down):
            ins_ = dict(ins, X=[x], Router=[router], Gate=[gate], Up=[up],
                        Down=[down])
            if "RouterInput" in ins:
                ins_["RouterInput"] = [x]
            outs = lm_ops.moe_ffn_op(None, ins_, attrs)
            grads = lm_ops.moe_ffn_grad_op(None, dict(
                ins_, **{"Out@GRAD": [x]},
                **{s: outs[s] for s in ("GateOut", "UpOut", "DownOut")}),
                attrs)
            return outs["Out"][0], [grads[s + "@GRAD"][0] for s in (
                "X", "Router", "Gate", "Up", "Down")]

        return str(jax.make_jaxpr(both)(
            x, router, ins["Gate"][0], ins["Up"][0], ins["Down"][0]))

    plain = text(ins, attrs)
    assert text(ins, dict(attrs, activation="silu")) == plain
    assert text(ins, dict(attrs, activation="relu")) != plain
    # a RouterInput that is X itself computes the same numbers through
    # another graph (two gradients where one sum was): the layer never
    # appends it (tests/test_smallthinker.py), and the text shows why
    assert text(dict(ins, RouterInput=[x]), attrs) != plain
