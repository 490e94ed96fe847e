"""Operations and bytes of the work of a decoder language model that holds
ONE CHIP'S SHARE of the experts and of the vocabulary, three of whose four
layers mix tokens with a GATED DELTA RULE behind a 4-tap convolution and
the fourth with output-gated grouped-query attention at heads of 256, every
layer with held experts beside a shared one (`qwen3_next_80b_a3b`), as
functions of the configuration's shapes and of the rows the held experts
really received. The triangle and the grouped-query bytes are
`costs_window_share`'s (`attention_flops`, `attention_bytes`: K and V read
once a key/value head), the generic pieces `costs_lm`'s.

THE DELTA RULE'S COUNT IS OF THE WORK, NOT OF THE IMPLEMENTATION. Bytes:
the least any lowering must move. Forward: q, k, v (the convolution's
output [T, 2 Hk dk + Hv dv]) and g, beta ([T, Hv] float32 each; as [b | a]
bf16 they are fewer: the float32 count is kept, it is 0.2% of the rest)
read, o [T, Hv dv] written, the chunk-start states [T / C, Hv, dk, dv]
float32 written once. Backward: those inputs and d o read, the five
gradients written, the chunk-start states read once. Operations: the
matrix products of the chunked (WY / UT-transform) form AT THE
CONFIGURATION'S STATED CHUNK (`delta_chunk`, 64), a chunk and value head:
k k^T and q k^T (2 x 2 C^2 dk), the unit-triangular solve on [C, dk + dv]
(C^2 (dk + dv)), W S, Q S and K^T U (3 x 2 C dk dv), P U (2 C^2 dv);
the backward twice the forward (every product has two gradients). A
lowering that uses another chunk, forms the triangular inverse by
squaring, computes q k^T twice for a key head's two value heads, or forms
the in-chunk quantities again in its backward does not change the count:
what it does more shows as a share below 100.

THE CONVOLUTION'S OP (`short_conv`, gating "silu") is bandwidth-bound: X
[T, channels] read and Out written forward; X and d Out read, d X written
backward.
"""

from chipbench.costs_lm import BF16, least_seconds, matmul_flops
from chipbench.costs_window_share import attention_bytes, attention_flops
from chipbench.reference.qwen3_next_80b_a3b import (DELTA, FULL, delta_dims,
                                                    layer_kinds as layers)

F32 = 4


def tokens(cfg):
    return cfg["rows_per_step"] * cfg["sequence_length"]


# -------------------------------------------------------------- delta rule
def delta_rule_flops_a_chunk(cfg):
    """Of one chunk of one value head, forward."""
    _, _, dk, dv, _ = delta_dims(cfg)
    C = cfg["delta_chunk"]
    return (2 * 2 * C * C * dk + C * C * (dk + dv) + 3 * 2 * C * dk * dv
            + 2 * C * C * dv)


def delta_rule_flops(cfg, train):
    _, hv, _, _, _ = delta_dims(cfg)
    chunks = cfg["rows_per_step"] * -(-cfg["sequence_length"]
                                      // cfg["delta_chunk"])
    return chunks * hv * delta_rule_flops_a_chunk(cfg) * (3 if train else 1)


def delta_rule_bytes(cfg, train):
    _, hv, dk, dv, conv = delta_dims(cfg)
    T = tokens(cfg)
    chunks = cfg["rows_per_step"] * -(-cfg["sequence_length"]
                                      // cfg["delta_chunk"])
    inputs = T * conv * BF16 + 2 * T * hv * F32
    out = T * hv * dv * BF16
    states = chunks * hv * dk * dv * F32
    forward = inputs + out + states
    # inputs and d o read, the five gradients written, the states read
    return forward + ((inputs + out + inputs + states) if train else 0)


def delta_rule_least_seconds(cfg, train, peaks):
    """Of the op of ONE delta layer over a step's rows."""
    return least_seconds(delta_rule_flops(cfg, train),
                         delta_rule_bytes(cfg, train), peaks)


def delta_rule_least_seconds_of(cfg, train, peaks):
    return layers(cfg).count(DELTA) * delta_rule_least_seconds(cfg, train,
                                                               peaks)


# ------------------------------------------------------------ short_conv op
def short_conv_flops(cfg, train):
    """L multiply-adds and a SiLU (4) an output element; the backward
    forms the convolution again, silu' (6), L multiply-adds for d X and L
    for d Filter."""
    n, L = tokens(cfg) * delta_dims(cfg)[4], cfg["linear_conv_kernel_dim"]
    return (2 * L + 4) * n + ((6 * L + 6) * n if train else 0)


def short_conv_bytes(cfg, train, elem=BF16):
    n = tokens(cfg) * delta_dims(cfg)[4] * elem
    return 2 * n + (3 * n if train else 0)


def short_conv_least_seconds_of(cfg, train, peaks):
    """Summed over every delta layer the program runs."""
    return layers(cfg).count(DELTA) * least_seconds(
        short_conv_flops(cfg, train), short_conv_bytes(cfg, train), peaks)


# --------------------------------------------------------------- attention
def attention_least_seconds_of(cfg, train, peaks):
    """Of the flash kernels of the full-attention layers over a step's
    rows: 16 query heads on 2 key/value heads of 256, the whole triangle."""
    rows, seq, d = cfg["rows_per_step"], cfg["sequence_length"], cfg["head_dim"]
    heads = cfg["num_attention_heads"]
    return layers(cfg).count(FULL) * least_seconds(
        attention_flops(rows, heads, seq, d, None, train),
        attention_bytes(rows, heads, cfg["num_key_value_heads"], seq, d,
                        train), peaks)


# ------------------------------------------------------------ expert layer
def grouped_kernels_per_step(cfg):
    """Grouped-matmul Pallas calls a training step makes: nine a layer."""
    return 9 * cfg["num_hidden_layers"]


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
    softmax, SiLU, the gates, top-k and the optimizer are left out, so a
    utilization built on this is slightly low, never high; the delta
    rule's matrix products at the stated chunk and the convolution's few
    operations an element ARE counted: they are the operator's own).
    `rows_held_per_token`: rows the held experts of a layer received over
    the tokens of the step (top_k x held / all if routing is even)."""
    C, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    _, hv, _, dv, conv = delta_dims(cfg)
    parts = dict.fromkeys(("delta_projections", "short_conv", "delta_rule",
                           "attention_projections", "attention", "router",
                           "held_experts", "shared_expert"), 0)
    for kind in layers(cfg):
        if kind == DELTA:
            parts["delta_projections"] += (
                matmul_flops(1, C, conv + hv * dv)
                + matmul_flops(1, C, 2 * hv) + matmul_flops(1, hv * dv, C))
            parts["short_conv"] += (2 * cfg["linear_conv_kernel_dim"]
                                    + 4) * conv
            parts["delta_rule"] += hv * delta_rule_flops_a_chunk(cfg) \
                / cfg["delta_chunk"]
        else:
            parts["attention_projections"] += (
                matmul_flops(1, C, 2 * heads * d)
                + 2 * matmul_flops(1, C, kv * d)
                + matmul_flops(1, heads * d, C))
            parts["attention"] += attention_flops(
                1, heads, seq, d, None, False) // seq
        parts["router"] += matmul_flops(
            1, C, cfg["deployment"]["num_experts"])
        parts["held_experts"] += rows_held_per_token * 3 * matmul_flops(
            1, C, cfg["moe_intermediate_size"])
        parts["shared_expert"] += 3 * matmul_flops(
            1, C, cfg["shared_expert_intermediate_size"]) \
            + matmul_flops(1, C, 1)
    parts["head"] = matmul_flops(1, C, cfg["vocab_size"])
    return parts


def train_flops_per_token(cfg, seq, rows_held_per_token):
    """Forward + backward (every product has two gradients): 3 x forward."""
    return 3 * sum(forward_flops_per_token(cfg, seq,
                                           rows_held_per_token).values())
