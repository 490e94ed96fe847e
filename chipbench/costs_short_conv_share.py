"""Operations and bytes of the work of a decoder language model that holds
ONE CHIP'S SHARE of the experts and of the vocabulary, most of whose layers
mix tokens with a GATED SHORT CONVOLUTION between two projections and the
rest with grouped-query attention at heads of 64, whose first layers have a
dense SwiGLU and the others held experts, and whose head is the embedding
table (`lfm2_8b_a1b`), as functions of the configuration's shapes and of
the rows the held experts really received. The triangle and the
grouped-query bytes are `costs_window_share`'s (`attention_flops`,
`attention_bytes`: K and V read once a key/value head; the head size is the
real 64, not a lane tile's 128), the generic pieces `costs_lm`'s.

THE CONV OPERATOR'S OP. `short_conv` is bandwidth-bound: its least bytes
are what any lowering must move, counted the same whatever lowers it
(XLA's fusions or a kernel). Forward: read X [T, 3C] once, write Out [T,
C]; backward: read X and d Out once, write d X [T, 3C] (d Filter is [L,
C]: nothing). The taps, the re-read of the L - 1 rows a block carries and
anything a lowering computes twice are not counted: a least time built on
these is never too high, so a roofline share built on it is never too
good. Its operations (2 L + 2 an output element forward) are counted too,
and lose to the bytes on every chip the table has.
"""

from chipbench.costs_lm import BF16, least_seconds, matmul_flops
from chipbench.costs_window_share import attention_bytes, attention_flops
from chipbench.reference.lfm2_8b_a1b import (ATTENTION, CONV, head_dim,
                                             layer_kinds as layers)


def sparse_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def tokens(cfg):
    return cfg["rows_per_step"] * cfg["sequence_length"]


# ------------------------------------------------------------ short_conv op
def short_conv_flops(cfg, train):
    """B * z, L multiply-adds, C * c an output element; the backward forms
    v and its convolution again, d c, d conv, L multiply-adds for d v, two
    gates, and L multiply-adds for d Filter."""
    n, L = tokens(cfg) * cfg["hidden_size"], cfg["conv_L_cache"]
    forward = (2 * L + 2) * n
    return forward + ((6 * L + 5) * n if train else 0)


def short_conv_bytes(cfg, train, elem=BF16):
    """Forward: X [T, 3C] in, Out [T, C] out. Backward: X and d Out in, d X
    [T, 3C] out."""
    n = tokens(cfg) * cfg["hidden_size"] * elem
    return (3 + 1) * n + ((3 + 1 + 3) * n if train else 0)


def short_conv_least_seconds(cfg, train, peaks):
    """Of the op of ONE conv layer over a step's rows, forward (and
    backward)."""
    return least_seconds(short_conv_flops(cfg, train),
                         short_conv_bytes(cfg, train), peaks)


def short_conv_least_seconds_of(cfg, train, peaks):
    """Summed over every conv layer the program runs."""
    return layers(cfg).count(CONV) * short_conv_least_seconds(cfg, train,
                                                              peaks)


# --------------------------------------------------------------- attention
def attention_least_seconds(cfg, train, peaks):
    """Of the flash kernels of ONE attention layer over a step's rows: 32
    query heads on 8 key/value heads of 64, the whole triangle."""
    rows, seq, d = cfg["rows_per_step"], cfg["sequence_length"], head_dim(cfg)
    heads = cfg["num_attention_heads"]
    return least_seconds(
        attention_flops(rows, heads, seq, d, None, train),
        attention_bytes(rows, heads, cfg["num_key_value_heads"], seq, d,
                        train), peaks)


def attention_least_seconds_of(cfg, train, peaks):
    return layers(cfg).count(ATTENTION) * attention_least_seconds(
        cfg, train, peaks)


# ------------------------------------------------------------ expert layer
def grouped_kernels_per_step(cfg):
    """Grouped-matmul Pallas calls a training step makes: nine a sparse
    layer."""
    return 9 * sparse_layers(cfg)


def expert_layer_least_seconds(cfg, rows_held, train, peaks):
    """Of the grouped products of ONE layer over the rows the held experts
    received: gate, up, down, in training each one's two gradients; each
    the larger of its operations and its bytes (the rows in, the held
    experts' matrices, the rows out)."""
    C, F, E = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    one = least_seconds(
        matmul_flops(rows_held, C, F),
        (rows_held * C + E * C * F + rows_held * F) * BF16, peaks)
    return (9 if train else 3) * one


# ------------------------------------------------------------- whole model
def forward_flops_per_token(cfg, seq, rows_held_per_token):
    """Operations one token's forward pass needs, by part (norms, rotary,
    softmax, SiLU, top-k and the optimizer are left out, so a utilization
    built on this is slightly low, never high; the conv op's few
    operations an element ARE counted: they are the operator's own).
    `rows_held_per_token`: rows the held experts of a sparse layer received
    over the tokens of the step (top_k x held / all if routing is even).
    The tied head is counted once: it is one product."""
    C, d = cfg["hidden_size"], head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    parts = dict.fromkeys(("conv_projections", "short_conv",
                           "attention_projections", "attention",
                           "dense_mlp", "router", "held_experts"), 0)
    for i, kind in enumerate(layers(cfg)):
        if kind == CONV:
            parts["conv_projections"] += (matmul_flops(1, C, 3 * C)
                                          + matmul_flops(1, C, C))
            parts["short_conv"] += (2 * cfg["conv_L_cache"] + 2) * C
        else:
            parts["attention_projections"] += (
                matmul_flops(1, C, heads * d)
                + 2 * matmul_flops(1, C, kv * d)
                + matmul_flops(1, heads * d, C))
            parts["attention"] += attention_flops(
                1, heads, seq, d, None, False) // seq
        if i < cfg["num_dense_layers"]:
            parts["dense_mlp"] += 3 * matmul_flops(
                1, C, cfg["intermediate_size"])
        else:
            parts["router"] += matmul_flops(
                1, C, cfg["deployment"]["num_experts"])
            parts["held_experts"] += rows_held_per_token * 3 * matmul_flops(
                1, C, cfg["moe_intermediate_size"])
    parts["head"] = matmul_flops(1, C, cfg["vocab_size"])
    return parts


def train_flops_per_token(cfg, seq, rows_held_per_token):
    """Forward + backward (every product has two gradients): 3 x forward."""
    return 3 * sum(forward_flops_per_token(cfg, seq,
                                           rows_held_per_token).values())
