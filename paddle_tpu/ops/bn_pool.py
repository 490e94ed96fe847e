"""A training `batch_norm` whose Y a global average `pool2d` reads (the
squeeze of an SE block), lowered together while a block is traced.

A reduction over an activation is produced where the activation is
produced, as per-sample [N, C] partial sums, and what needs a reduction of
it downstream is algebra on those sums:

  forward   s1 = sum_hw(x) is the first stage of the batch norm's mean, and
            mean_hw(Y) = (s1/HW - m) * inv * scale + bias: the pool takes
            no pass of its own over the block's widest tensor.
  backward  in an SE block, out = Y * gate[n, c] with gate a function of the
            pool, batch norm's dy is d_out * gate + d_pool / HW. Its two
            reductions and the gate's gradient sum_hw(d_out * Y) follow
            from A = sum_hw(d_out) and B = sum_hw(d_out * x_hat), which XLA
            emits from the fusion that makes d_out; dy is never written.

Everything about the pair is here: which ops are one (`match`, `count`),
the attr that asks `batch_norm`'s kernel for the sums (PER_SAMPLE_SUMS),
and `Lowering`, which `executor_core.run_ops` offers every op of a traced
block. The `Program` is never rewritten. The backward is engaged only where
a pair's dataflow is exactly the SE block's, followed by the identity of
the traced values (which no later write to a name can change); a pair that
sees anything else refuses for good and the generic kernels run, whose
results are the same numbers.
"""

import jax.numpy as jnp

from .util import bcast_y_to_x

# Attr of ONE kernel call at trace time, never of a Program: `batch_norm`
# takes its mean in two stages through s1 and returns the slots SampleSum
# (s1) and PooledY (mean_hw(Y), float32 [N, C]) beside its own.
PER_SAMPLE_SUMS = "@per_sample_sums"


def _global_avg_pool(op):
    return op.attrs.get("global_pooling", False) \
        and op.attrs.get("pooling_type", "max") == "avg"


def match(ops):
    """{id(batch_norm op): the pool2d op lowered with it}: training-mode
    batch norms whose Y a global average pool2d of the same layout reads
    directly (Y may have other readers, as the SE scale multiply is). The
    pool's Out is written where the batch norm runs, so no op between the
    two may touch that name, nor rewrite Y."""
    pairs, live = {}, {}  # live: Y name -> (index, batch_norm op)
    for j, op in enumerate(ops):
        if op.type == "pool2d" and _global_avg_pool(op):
            i, bn = live.get(op.input("X")[0], (None, None))
            if bn is not None and id(bn) not in pairs \
                    and op.attrs.get("data_format", "NCHW") \
                    == bn.attrs.get("data_layout", "NCHW") \
                    and not any(
                        op.output("Out")[0] in o.input_arg_names()
                        + o.output_arg_names() for o in ops[i:j]):
                pairs[id(bn)] = op
        for n in op.output_arg_names():
            live.pop(n, None)
        if op.type == "batch_norm" and not op.attrs.get("is_test", False):
            live[op.output("Y")[0]] = (j, op)
    return pairs


def count(program):
    """How many pairs a training trace of `program` lowers together (the
    monitor counter and step span attr `fused_bn_global_pool`). `Lowering`
    takes every pair `match` finds — a pool2d's input is 4-D or the program
    does not run — so the number needs no trace, and a step whose
    executable was loaded from the persistent cache has it too. Read at
    every step of a traced or monitored run, so it is kept on the program
    until that is mutated."""
    memo = getattr(program, "_fused_bn_global_pool", None)
    if memo is None or memo[0] != program._mutation:
        memo = program._fused_bn_global_pool = (program._mutation, sum(
            len(match(b.ops)) for b in program.blocks))
    return memo[1]


class _Pair:
    """One pair lowered together, and how far its backward has been
    recognised. Each part of the backward is set once: by the gradient of
    the one multiply with a per-sample gate (gate, d_out, its sums A and B,
    dy_gate), by the pool's (d_pool, dy_pool), and by the `sum` of exactly
    dy_gate and dy_pool (dy). Anything else that touches Y's gradient
    calls refuse(), after which batch_norm_grad is the generic kernel."""

    def __init__(self, x, y, layout, scale, bias, mean, inv, sample_sum):
        self.x, self.y, self.scale, self.bias = x, y, scale, bias
        self.mean, self.inv, self.sample_sum = mean, inv, sample_sum
        self.hw_axes = (1, 2) if layout == "NHWC" else (2, 3)
        n, c = sample_sum.shape
        self.nc = (n, 1, 1, c) if layout == "NHWC" else (n, c, 1, 1)
        self.c = (1,) + self.nc[1:]
        self.hw = x.size // (n * c)
        self.refused = False
        self.gate = self.d_out = self.sums = self.dy_gate = None
        self.d_pool = self.dy_pool = self.dy = None

    def refuse(self):
        self.refused = True
        self.dy_gate = self.dy_pool = self.dy = None

    def x_hat(self):
        return (self.x.astype(jnp.float32) - self.mean.reshape(self.c)) \
            * self.inv.reshape(self.c)

    def sample_sums(self, d_out):
        """A = sum_hw(d_out), B = sum_hw(d_out * x_hat), [N, C] each."""
        d_out = d_out.astype(jnp.float32)
        return (jnp.sum(d_out, axis=self.hw_axes),
                jnp.sum(d_out * self.x_hat(), axis=self.hw_axes))

    def gate_grad(self, a, b):
        """sum_hw(d_out * Y) [N, C], Y = x_hat * scale + bias."""
        return self.scale * b + self.bias * a

    def grad(self):
        """(dX, dScale, dBias) of the batch norm, dy = d_out * gate +
        d_pool / HW, in float32 before the one cast of dX."""
        a, b = self.sums
        n = self.sample_sum.shape[0]
        gate = self.gate.astype(jnp.float32).reshape(n, -1)
        d_pool = self.d_pool.astype(jnp.float32).reshape(n, -1) / self.hw
        sum_x_hat = (self.sample_sum - self.hw * self.mean) * self.inv
        d_bias = jnp.sum(gate * a + d_pool * self.hw, axis=0)
        d_scale = jnp.sum(gate * b + d_pool * sum_x_hat, axis=0)
        count = n * self.hw
        dy = self.d_out.astype(jnp.float32) * gate.reshape(self.nc) \
            + d_pool.reshape(self.nc)
        dx = (self.scale * self.inv).reshape(self.c) * (
            dy - (d_bias / count).reshape(self.c)
            - self.x_hat() * (d_scale / count).reshape(self.c))
        return dx.astype(self.x.dtype), d_scale, d_bias


def _bn_key(op):
    return tuple(op.inputs.get(s, [None])[0] for s in ("X", "Scale", "Bias"))


class Lowering:
    """What executor_core.run_ops offers every op of one traced block.
    `run_op(op, env, ctx, attrs=None) -> outs` runs an op's kernel and
    binds its outputs; `bind(op, outs, env, ctx)` binds outputs computed
    here; `scope(op, ctx)` is the named scope `run_op` lowers an op under,
    for what is computed here in its place: the sums and the gate's
    gradient carry the scope of the `elementwise_mul_grad` that offered
    them, the pair's dX, dScale and dBias that of the `batch_norm_grad`,
    and the pooled mean that of the `batch_norm` (it is a slot of that
    kernel's call; only its cast carries the pool's)."""

    def __init__(self, ops, ctx, run_op, bind, scope):
        # a trace in test mode takes no batch statistics: nothing to ride
        self.pool_of = {} if ctx.is_test else match(ops)
        self.run_op, self.bind, self.scope = run_op, bind, scope
        self.pooled = set()   # id of the pool ops written with their norm
        self.by_key = {}      # _bn_key -> _Pair, for its batch_norm_grad
        self.by_value = {}    # id(Y, or a part of Y's gradient) -> _Pair

    def offer(self, op, env, ctx):
        """True when the op has been lowered here."""
        if id(op) in self.pooled:
            return True
        pool = self.pool_of.get(id(op))
        if pool is not None:
            return self._forward(op, pool, env, ctx)
        handler = self.by_key and self._BACKWARD.get(op.type)
        return bool(handler) and handler(self, op, env, ctx)

    def _forward(self, bn, pool, env, ctx):
        from .. import amp

        x = env.get(bn.input("X")[0])
        if getattr(x, "ndim", None) != 4:
            return False  # not a pool2d input: that kernel says so
        outs = self.run_op(bn, env, ctx, {**bn.attrs, PER_SAMPLE_SUMS: True})
        y = env[bn.output("Y")[0]]
        pair = _Pair(x, y, pool.attrs.get("data_format", "NCHW"),
                     env[bn.input("Scale")[0]], env[bn.input("Bias")[0]],
                     outs["SavedMean"][0], outs["SavedVariance"][0],
                     outs["SampleSum"][0])
        # in the dtype and shape pool2d would have given it
        with self.scope(pool, ctx):
            pooled = amp.apply_policy(
                "pool2d",
                {"X": [outs["PooledY"][0].astype(y.dtype)]})["X"][0]
        self.bind(pool, {"Out": [pooled.reshape(pair.nc)]}, env, ctx)
        self.pooled.add(id(pool))
        self.by_key[_bn_key(bn)] = self.by_value[id(y)] = pair
        return True

    # -- backward: each handler runs the generic kernel, and records what
    # -- it recognises of the SE block's dataflow on the pair
    def _reader_of_y(self, op, env):
        x = op.input("X")
        pair = self.by_value.get(id(env.get(x[0]))) if len(x) == 1 else None
        return pair if pair is not None and env.get(x[0]) is pair.y else None

    def _mul_grad(self, op, env, ctx):
        pair = self._reader_of_y(op, env)
        if pair is None:
            return False
        outs = self.run_op(op, env, ctx)
        gate, d_out = env.get(op.input("Y")[0]), env.get(
            op.input("Out@GRAD")[0])
        dy_gate = (outs.get("X@GRAD") or [None])[0]
        if pair.refused or pair.dy_gate is not None or dy_gate is None \
                or bcast_y_to_x(pair.x, gate, op.attrs.get("axis", -1)).shape \
                != pair.nc:
            pair.refuse()  # a second multiply of Y, or not a gate
            return True
        pair.gate, pair.d_out, pair.dy_gate = gate, d_out, dy_gate
        self.by_value[id(dy_gate)] = pair
        generic = (outs.get("Y@GRAD") or [None])[0]
        with self.scope(op, ctx):
            pair.sums = pair.sample_sums(d_out)
            if generic is not None:
                generic = pair.gate_grad(*pair.sums).astype(
                    generic.dtype).reshape(generic.shape)
        if generic is not None:
            self.bind(op, {"Y@GRAD": [generic]}, env, ctx)
        return True

    def _pool_grad(self, op, env, ctx):
        pair = self._reader_of_y(op, env)
        if pair is None:
            return False
        outs = self.run_op(op, env, ctx)
        dy_pool = (outs.get("X@GRAD") or [None])[0]
        if pair.refused or pair.dy_pool is not None or dy_pool is None \
                or not _global_avg_pool(op):
            pair.refuse()  # another pool of Y
            return True
        pair.d_pool, pair.dy_pool = env.get(op.input("Out@GRAD")[0]), dy_pool
        self.by_value[id(dy_pool)] = pair
        return True

    def _sum(self, op, env, ctx):
        parts = [env.get(n) for n in op.input("X")]
        pair = next((p for p in map(self.by_value.get, map(id, parts))
                     if p is not None), None)
        if pair is None:
            return False
        outs = self.run_op(op, env, ctx)
        if pair.dy is None and pair.dy_gate is not None \
                and pair.dy_pool is not None and len(parts) == 2 \
                and {id(v) for v in parts} \
                == {id(pair.dy_gate), id(pair.dy_pool)}:
            pair.dy = outs["Out"][0]
        else:
            pair.refuse()  # Y has a reader the algebra does not cover
        return True

    def _bn_grad(self, op, env, ctx):
        pair = self.by_key.get(_bn_key(op))
        if pair is None:
            return False
        if pair.dy is not None and env.get(op.input("X")[0]) is pair.x \
                and env.get(op.input("Y@GRAD")[0]) is pair.dy \
                and {s for s, n in op.outputs.items() if any(n)} \
                <= {"X@GRAD", "Scale@GRAD", "Bias@GRAD"}:
            with self.scope(op, ctx):
                dx, d_scale, d_bias = pair.grad()
            self.bind(op, {"X@GRAD": [dx], "Scale@GRAD": [d_scale],
                           "Bias@GRAD": [d_bias]}, env, ctx)
        else:
            # the vjp re-traces the forward: with the same statistics its
            # recomputation is the forward's own expression
            self.run_op(op, env, ctx, {**op.attrs, PER_SAMPLE_SUMS: True})
        return True

    _BACKWARD = {"elementwise_mul_grad": _mul_grad, "pool2d_grad": _pool_grad,
                 "sum": _sum, "batch_norm_grad": _bn_grad}

