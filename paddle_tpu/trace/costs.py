"""Analytic per-op FLOP estimates of a ProgramDesc, from operand / result
shapes (the standard 2*M*K*N-style counts): what `parallel/autoshard`,
`parallel/pipeline` and `analysis/schedule` weigh ops by before anything
has run. An estimate, never a measurement: where a step's device time
goes by op is read from a device trace, whose operations carry the Fluid
op they came from (docs/observability.md, "The Fluid op in a device
trace").
"""

__all__ = ["op_costs"]

# ops that move/bookkeep but do no arithmetic worth attributing
_FREE_OPS = frozenset((
    "feed", "fetch", "fill_constant", "shape", "read", "read_from_array",
    "write_to_array", "increment", "assign", "share_lod", "print",
))

# per-element arithmetic weight for ops whose cost ~ output size; the
# default (1 flop/elem) covers the elementwise/copy family
_ELEM_WEIGHTS = {
    "softmax": 5.0, "log_softmax": 5.0, "sigmoid": 4.0, "tanh": 4.0,
    "exp": 4.0, "log": 4.0, "sqrt": 2.0, "rsqrt": 2.0,
    "batch_norm": 8.0, "layer_norm": 8.0, "group_norm": 8.0,
    "dropout": 2.0, "cross_entropy": 5.0,
    "softmax_with_cross_entropy": 10.0, "sigmoid_cross_entropy_with_logits":
    8.0, "swish": 4.0, "gelu": 8.0, "elu": 3.0, "selu": 3.0,
    # x^2 and its mean, x r w (4), a SiLU (4), the gate's product
    "gated_rms_norm": 11.0,
}


def _numel(shape, batch):
    n = 1
    for d in shape or ():
        d = batch if (d is None or int(d) < 0) else int(d)
        n *= max(1, d)
    return max(1, n)


def _shape_of(block, name, batch):
    var = block.vars.get(name)
    if var is None and hasattr(block, "var_recursive"):
        try:
            var = block.var_recursive(name)
        except Exception:
            var = None
    return None if var is None else (var.shape or ())


def _estimate(block, op, batch):
    """Analytic FLOPs for one op (forward form); returns float."""
    t = op.type
    outs = op.output_arg_names()
    out_elems = _numel(_shape_of(block, outs[0], batch), batch) \
        if outs else 1

    if t in ("mul", "matmul", "matmul_v2"):
        # X [.., K] x Y [K, N]: 2*M*K*N with M = numel(X)/K
        xs = op.input("X") or op.input_arg_names()[:1]
        ys = op.input("Y") or op.input_arg_names()[1:2]
        x_shape = _shape_of(block, xs[0], batch) if xs else None
        y_shape = _shape_of(block, ys[0], batch) if ys else None
        if x_shape and y_shape:
            k = max(1, _numel(y_shape[:1], batch))
            m = _numel(x_shape, batch) / k
            n = _numel(y_shape, batch) / k
            return 2.0 * m * k * n
        return 2.0 * out_elems
    if t in ("conv2d", "depthwise_conv2d", "conv2d_transpose", "conv3d"):
        fs = op.input("Filter")
        f_shape = _shape_of(block, fs[0], batch) if fs else None
        if f_shape and len(f_shape) >= 3:
            # [Cout, Cin/groups, kh, kw]: 2 * out * Cin_g * prod(k)
            per_out = 2.0
            for d in f_shape[1:]:
                per_out *= max(1, int(d) if d is not None and d > 0 else 1)
            return out_elems * per_out
        return 2.0 * out_elems
    if t == "short_conv":
        # B * z, L multiply-adds a channel, C * c: 2 L + 2 an output
        fs = op.input("Filter")
        f_shape = _shape_of(block, fs[0], batch) if fs else None
        taps = int(f_shape[0]) if f_shape else 3
        if op.attrs.get("gating"):
            # L multiply-adds and a SiLU (4) an output
            return out_elems * (2.0 * taps + 4.0)
        return out_elems * (2.0 * taps + 2.0)
    if t == "gated_delta_rule":
        # the chunked form a token and value head: q k^T and k k^T (4 C
        # dk), the triangular solve (C (dk + dv)), W S, Q S and K^T U (6 dk
        # dv), P U (2 C dv)
        a = op.attrs
        c, dk, dv = (int(a.get(k, d)) for k, d in (
            ("chunk", 64), ("head_k_dim", 128), ("head_v_dim", 128)))
        return out_elems / dv * (4.0 * c * dk + c * (dk + dv)
                                 + 6.0 * dk * dv + 2.0 * c * dv)
    if t == "ssd_scan":
        # the chunked form a token and head: C B^T a group (2 Q N G / H),
        # the masked product with x (2 Q P), the chunk's own state and the
        # carried part (4 P N)
        a = op.attrs
        q, p, n, h, g = (int(a.get(k, d)) for k, d in (
            ("chunk", 128), ("head_dim", 64), ("state_size", 128),
            ("num_heads", 64), ("num_groups", 8)))
        return out_elems / p * (2.0 * q * n * g / h + 2.0 * q * p
                                + 4.0 * p * n)
    if t in ("indexer_select", "indexer_loss"):
        # the indexer's scores over the causal pairs: 2 Hi Di a pair (the
        # loss forms them again with their two gradients, and the main
        # attention's scores once: 2 H D a pair)
        qi = _shape_of(block, op.input("QI")[0], batch)
        B, S, hi, di = (max(1, int(d)) if d and int(d) > 0 else batch
                        for d in qi)
        pairs = B * S * (S + 1) / 2.0
        if t == "indexer_select":
            return pairs * 2.0 * hi * di
        q = _shape_of(block, op.input("Q")[0], batch)
        return pairs * (6.0 * hi * di + 2.0 * max(1, int(q[2]))
                        * max(1, int(q[3])))
    if t == "sparse_attention":
        # Q K^T and P V over the chosen pairs: the mask's `topk` is the
        # selecting op's, so the triangle stands in (an upper bound)
        q = _shape_of(block, op.input("Q")[0], batch)
        S = max(1, int(q[1])) if q[1] and int(q[1]) > 0 else batch
        return out_elems * 2.0 * (S + 1)
    if t in ("pool2d", "pool3d"):
        k = op.attrs.get("ksize") or []
        kk = 1.0
        for d in k:
            kk *= max(1, int(d))
        if op.attrs.get("global_pooling"):
            ins = op.input("X")
            in_shape = _shape_of(block, ins[0], batch) if ins else None
            if in_shape and len(in_shape) >= 2:
                kk = _numel(in_shape, batch) / max(1, out_elems)
        return out_elems * kk
    if t.startswith("reduce_") or t in ("mean", "sum"):
        ins = op.input("X") or op.input_arg_names()[:1]
        in_shape = _shape_of(block, ins[0], batch) if ins else None
        return float(_numel(in_shape, batch)) if in_shape is not None \
            else float(out_elems)
    if t in ("lookup_table", "gather", "concat", "split", "transpose",
             "reshape", "squeeze", "unsqueeze", "cast", "scale", "pad"):
        return float(out_elems)
    if t in ("pipeline_send", "pipeline_recv", "zero1_gather",
             "all_gather", "broadcast"):
        # pure data movement (ICI): attribute the moved elements
        return float(out_elems)
    if t in ("zero1_scatter", "all_reduce", "reduce_scatter"):
        # ring reduction: ~one add per input element around the ring
        ins = op.input("X") or op.input_arg_names()[:1]
        in_shape = _shape_of(block, ins[0], batch) if ins else None
        return float(_numel(in_shape, batch)) if in_shape is not None \
            else float(out_elems)
    if t.endswith("_grad"):
        # grad ops roughly mirror the forward cost for input grads plus
        # a comparable pass for parameter grads
        fwd = _OpProxy(op, t[:-len("_grad")])
        return 2.0 * _estimate(block, fwd, batch)
    return _ELEM_WEIGHTS.get(t, 1.0) * out_elems


class _OpProxy:
    """An op view with a substituted type (grad -> forward estimation)."""

    __slots__ = ("_op", "type")

    def __init__(self, op, type_):
        self._op = op
        self.type = type_

    def __getattr__(self, name):
        return getattr(self._op, name)


def op_costs(program, batch_size=1):
    """Analytic per-op FLOP estimates over the global block:
    [{"index", "op", "out", "flops_est"}] in program order."""
    gb = program.global_block()
    batch = max(1, int(batch_size))
    rows = []
    for i, op in enumerate(gb.ops):
        if op.type in _FREE_OPS:
            continue
        try:
            est = float(_estimate(gb, op, batch))
        except Exception:
            est = 0.0
        outs = op.output_arg_names()
        rows.append({"index": i, "op": op.type,
                     "out": outs[0] if outs else "", "flops_est": est})
    return rows
