"""Automatic mixed precision: bf16 compute with fp32 master state.

Reference parity: paddle/contrib/float16/float16_transpiler.py:1 — a program
rewrite that inserts cast ops around float16-capable ops and converts
parameters. TPU-native design: the executor applies this dtype policy while
tracing the block to XLA, so the inserted `convert_element_type` HLOs are
exactly the reference's cast ops, but placed at trace time — one program can
run fp32 or bf16 without cloning, and XLA fuses the casts into neighbours.

Recipe (the canonical TPU one):
  * white-list ops (matmul/conv/pool/activations — where the MXU FLOPs are)
    cast float32 inputs down to the compute dtype; their outputs stay bf16 so
    whole residual chains flow at half the HBM traffic;
  * black-list ops (losses, softmax, reductions/grad-accumulation, optimizer
    updates, metrics) cast bf16 inputs up to float32 — parameters and
    optimizer accumulators therefore remain fp32 "master weights" and every
    state update happens in fp32;
  * batch_norm/layer_norm are dtype-preserving but already compute their
    statistics in fp32 internally (ops/nn_ops.py), so they stay neutral;
  * bf16 shares float32's exponent range, so no loss scaling is required
    (`scale_loss` exists for float16 experiments).

Gradient ops inherit the classification of their forward op (`mul_grad`
follows `mul`), so the backward pass mirrors the forward dtype flow and
parameter gradients are upcast exactly once, at the optimizer/sum boundary.
"""

import collections
import contextlib

import numpy as np

__all__ = ["auto_cast", "enable", "disable", "is_enabled", "fingerprint",
           "WHITE_LIST", "BLACK_LIST", "scale_loss"]

# Ops whose float inputs are cast DOWN to the compute dtype: MXU compute,
# memory-bound activations, and the elementwise glue between them. Pure
# data-movement ops (reshape/transpose/concat/...) are deliberately absent —
# they preserve whatever dtype arrives, so the bf16 flow rides through them
# without risking a downcast of unrelated fp32 tensors (LR schedules etc.).
WHITE_LIST = frozenset({
    "mul", "matmul", "fc",
    "conv2d", "conv3d", "conv2d_transpose", "depthwise_conv2d",
    "pool2d", "maxout",
    "relu", "relu6", "leaky_relu", "brelu", "prelu", "tanh", "sigmoid",
    "elu", "soft_relu",
    "dropout",
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "lstm", "gru", "lstm_unit", "gru_unit", "sequence_conv", "row_conv",
    "attention_lstm_decoder", "im2sequence",
    # the flash kernel and the grouped expert products take bf16 operands
    # and accumulate in float32; rms_norm, gated_rms_norm and
    # rotary_embedding stay neutral (dtype-preserving, float32 inside, like
    # layer_norm: inputs arrive as they are, a Scale stays the float32
    # master)
    "causal_attention", "moe_ffn",
    # the gated short convolution between two `fc` products: bf16 in and
    # out, its gates and taps float32 inside (ops/lm_ops.py: short_conv)
    "short_conv",
    # the gated delta rule behind that convolution: bf16 q, k, v in and o
    # out, g, beta, the decays and the carried state float32 inside
    # (parallel/delta_rule.py)
    "gated_delta_rule",
    # the selective state-space scan behind such a convolution: bf16 x, B,
    # C, dt in and y out, the steps, every decay and the carried state
    # float32 inside (parallel/ssd.py)
    "ssd_scan",
    # attention over an indexer's selection: the masked flash kernels take
    # bf16 operands as `causal_attention`'s do (`indexer_select` and
    # `indexer_loss` stay neutral: they take what arrives and score in
    # float32 inside)
    "sparse_attention",
    # the residual path of n streams: the state and the sublayers' outputs
    # flow in the compute dtype, the mixers themselves are float32 inside
    "mhc_expand", "mhc_mix", "mhc_update",
})

# Input slots of a white-list op that are NOT cast down: moe_ffn's router
# weight (router matmul, softmax and top-k run in float32: a bf16 logit
# flips the discrete choice between near-tied experts), the rows the router
# reads where the program names them apart from the experts' input (they
# arrive as they are: float32 embedding rows stay float32) and the incoming
# gradients of its two float32 scalar losses.
FLOAT32_SLOTS = {
    "moe_ffn": frozenset({"Router", "RouterInput", "Bias", "AuxLoss@GRAD",
                          "ZLoss@GRAD"}),
    # the mixers' parameters and the per-token mixing matrices they make
    "mhc_mix": frozenset({"PhiPre", "PhiPost", "PhiRes", "Alpha", "BPre",
                          "BPost", "BRes", "HPost@GRAD", "HRes@GRAD"}),
    "mhc_update": frozenset({"HRes", "HPost"}),
    # the taps [L, C] and the "silu" variant's bias [C]: float32 masters
    # read as they are
    "short_conv": frozenset({"Filter", "Bias"}),
    # A_log, dt_bias: [Hv] float32 masters read as they are; the saved
    # chunk-start states stay float32
    "gated_delta_rule": frozenset({"ALog", "DtBias", "States"}),
    "ssd_scan": frozenset({"ALog", "DtBias", "D", "States"}),
    # the saved logsumexp: the backward's weights are exp(s - lse)
    "sparse_attention": frozenset({"Lse"}),
}

# Input slots of a white-list op whose value the lowering hands to a Pallas
# call as it stands. Such a call fuses no producer, so the policy's cast of
# a float32 master read through one of these is a pass of its own through
# HBM, every step. A parameter read so is kept in the compute dtype beside
# its master instead, where its update op is its only writer: the update
# writes the copy from the value it has just computed (`LOW_OUT`,
# optimizer.py), and the step hands the op the copy (`kept_copy`).
KERNEL_SLOTS = {
    "moe_ffn": frozenset({"Gate", "Up", "Down"}),
}
# the update op's output that holds ParamOut in the compute dtype
LOW_OUT = "ParamLowOut"

# Ops whose bf16 inputs are cast UP to float32 (numerics-sensitive math,
# gradient accumulation, every optimizer/state update, metrics).
BLACK_LIST = frozenset({
    "softmax", "sequence_softmax", "log_softmax",
    "cross_entropy", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "huber_loss", "hinge_loss",
    "smooth_l1_loss", "log_loss", "rank_loss", "margin_rank_loss",
    "square_error_cost", "squared_l2_distance", "squared_l2_norm",
    "cos_sim", "cumsum",
    "mean",
    # NOTE: "sum" (elementwise multi-input add — residual-junction grad
    # accumulation) is deliberately NEUTRAL: upcasting every activation-grad
    # merge to fp32 doubles HBM traffic on the backward pass, and a 2-term
    # bf16 add loses nothing. Param-grad sums still end in a black optimizer
    # op, so master updates stay fp32.
    "norm", "lrn",
    "clip_by_norm", "isfinite",
    "sgd", "momentum", "adam", "adamax", "adagrad", "adadelta",
    "decayed_adagrad", "rmsprop", "ftrl",
    "accuracy", "auc", "precision_recall", "edit_distance", "chunk_eval",
    "exp", "log", "sqrt", "reciprocal", "pow", "softplus",
})

# The casts the policy inserts are lowered under this named scope inside
# the op's own (`moe/moe_ffn/cast`): where a cast is a pass of its own, as
# the float32 -> bf16 casts of an expert layer's weights are, a device
# trace names it (a cast XLA fuses into its consumer carries the
# consumer's name).
CAST_SCOPE = "cast"

_state = {
    "enabled": False,
    "dtype": "bfloat16",
    "white": WHITE_LIST,
    "black": BLACK_LIST,
}


def enable(dtype="bfloat16", custom_white_list=None, custom_black_list=None):
    """Turn the mixed-precision policy on for subsequent executor traces.

    custom_white_list / custom_black_list EXTEND the defaults (an op may be
    moved between lists by naming it in the other one — explicit custom
    entries win over the defaults)."""
    white = set(WHITE_LIST)
    black = set(BLACK_LIST)
    if custom_white_list:
        white |= set(custom_white_list)
        black -= set(custom_white_list)
    if custom_black_list:
        black |= set(custom_black_list)
        white -= set(custom_black_list)
    _state.update(enabled=True, dtype=dtype,
                  white=frozenset(white), black=frozenset(black))


def disable():
    _state["enabled"] = False


def is_enabled():
    return _state["enabled"]


def compute_dtype():
    return _state["dtype"]


def fingerprint():
    """Hashable policy signature — part of every executor compile-cache key
    (a cached fp32 step must not be reused after enabling bf16). Sorted
    tuples, not hash(frozenset): the signature also feeds the PERSISTENT
    compile-cache digest, which must be stable across processes
    (PYTHONHASHSEED makes hash() process-local)."""
    if not _state["enabled"]:
        return ("amp-off",)
    return ("amp", _state["dtype"],
            tuple(sorted(_state["white"])), tuple(sorted(_state["black"])))


@contextlib.contextmanager
def auto_cast(enabled=True, dtype="bfloat16",
              custom_white_list=None, custom_black_list=None):
    """Context manager; policy is read at executor trace time, so wrap the
    exe.run / ParallelExecutor.run calls (reference fluid.amp.auto_cast)."""
    prev = dict(_state)
    try:
        if enabled:
            enable(dtype, custom_white_list, custom_black_list)
        else:
            disable()
        yield
    finally:
        _state.update(prev)


# ---------------------------------------------------------------------------
# Trace-time cast application (called from core.registry.run_kernel)
# ---------------------------------------------------------------------------
def _base_type(op_type):
    return op_type[:-5] if op_type.endswith("_grad") else op_type


def _cast_value(v, target, only_from=None):
    """Cast a float array (or SeqTensor data) to `target`; ints/bools and
    None pass through. `only_from` restricts which source dtypes convert."""
    import jax
    import jax.numpy as jnp
    from .core.registry import SeqTensor

    if v is None:
        return v
    if isinstance(v, SeqTensor):
        d = _cast_value(v.data, target, only_from)
        return v if d is v.data else SeqTensor(d, v.lengths)
    from .core.selected_rows import SelectedRows
    if isinstance(v, SelectedRows):
        d = _cast_value(v.values, target, only_from)
        return v if d is v.values else SelectedRows(v.rows, d, v.height)
    if not hasattr(v, "dtype"):
        return v
    kind = np.dtype(v.dtype) if not isinstance(v.dtype, np.dtype) else v.dtype
    name = str(v.dtype)
    if kind.kind != "f" and name != "bfloat16":
        return v
    if only_from is not None and name not in only_from:
        return v
    if name == target:
        return v
    with jax.named_scope(CAST_SCOPE):
        return jnp.asarray(v).astype(target)


def kernel_slots(op_type):
    """The slots of `KERNEL_SLOTS` in which the policy as it stands casts
    what an op of this type reads down to the compute dtype: none with
    the policy off or the op off the white list."""
    base = _base_type(op_type)
    if not _state["enabled"] or base not in _state["white"]:
        return ()
    return KERNEL_SLOTS.get(base, ())


def kernel_read_params(program):
    """Names `program` reads through a slot of `KERNEL_SLOTS` and none of
    its ops writes: the parameters whose update, appended now with the
    policy on, keeps a copy in the compute dtype. None with the policy
    off."""
    read, written = set(), set()
    for block in program.blocks:
        for op in block.ops:
            written.update(op.output_arg_names())
            for slot in kernel_slots(op.type):
                read.update(n for n in op.inputs.get(slot, ()) if n)
    return frozenset(read - written)


def kept_copies(program):
    """({parameter: its kept copy} a step of `program` may read, the names
    of all the copies its update ops write). A step reads the copy of a
    parameter whose update op is its only writer in the program: after any
    other write the copy would not be the cast of the master. Read off the
    update ops, kept on the program until that is mutated (as
    `lm_ops.lowered_counts`)."""
    memo = getattr(program, "_kept_copies", None)
    if memo is None or memo[0] != program._mutation:
        ops = [op for b in program.blocks for op in b.ops]
        writers = collections.Counter(
            n for op in ops for n in op.output_arg_names())
        declared = {op.input("Param")[0]: op.output(LOW_OUT)[0]
                    for op in ops if op.outputs.get(LOW_OUT)}
        memo = program._kept_copies = (
            program._mutation,
            {p: c for p, c in declared.items() if writers[p] == 1},
            frozenset(declared.values()))
    return memo[1], memo[2]


def reads_kept_copies(op):
    """Whether a step lowered under the policy as it stands hands `op` a
    kept copy in every slot of `KERNEL_SLOTS` (`kept_copy`)."""
    slots = kernel_slots(op.type)
    program = op.block.program
    kept, gvars = kept_copies(program)[0], program.global_block().vars
    return bool(slots) and all(
        n in kept and gvars[kept[n]].dtype == _state["dtype"]
        for slot in slots for n in op.input(slot))


def kept_copy(value, copy):
    """What an op receives in a slot of `kernel_slots`: `copy`, the kept
    copy of the parameter whose master `value` is, where `apply_policy`
    would cast `value` to just that (the copy is there, in the policy's
    compute dtype); `value` otherwise, for the policy to cast as ever."""
    if copy is None or str(copy.dtype) != _state["dtype"] \
            or str(getattr(value, "dtype", None)) != "float32" \
            or copy.shape != value.shape:
        return value
    return copy


def apply_policy(op_type, ins):
    """Return `ins` with the dtype policy applied for op `op_type`."""
    if not _state["enabled"]:
        return ins
    base = _base_type(op_type)
    if base in _state["white"]:
        target, only_from = _state["dtype"], ("float32", "float64")
    elif base in _state["black"]:
        target, only_from = "float32", ("bfloat16", "float16")
    else:
        return ins
    keep = FLOAT32_SLOTS.get(base, ()) if target != "float32" else ()
    changed = False
    new_ins = {}
    for slot, vals in ins.items():
        nv = vals if slot in keep else [
            _cast_value(v, target, only_from) for v in vals]
        changed = changed or any(a is not b for a, b in zip(nv, vals))
        new_ins[slot] = nv
    return new_ins if changed else ins


@contextlib.contextmanager
def scale_loss(loss_scaling=1.0):
    """Loss-scaling hook for float16 experiments (reference float16 needs
    it; bf16 does not — kept for API parity). Yields the scale to multiply
    the loss by; divide gradients by the same factor before applying."""
    yield float(loss_scaling)
