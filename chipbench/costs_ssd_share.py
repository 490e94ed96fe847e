"""Operations and bytes of the work of a decoder language model that holds
ONE CHIP'S SHARE of the experts and of the vocabulary and whose layers are
one branch each: Mamba-2 mixers (a selective state-space scan behind a
4-tap convolution), grouped-query attention without positions, un-gated
relu^2 experts beside a shared one (`nemotron_3_nano_30b_a3b`), as
functions of the configuration's shapes and of the rows the held experts
really received. The triangle and the grouped-query bytes are
`costs_window_share`'s (`attention_flops`, `attention_bytes`: K and V read
once a key/value head), the generic pieces `costs_lm`'s.

THE SCAN'S COUNT IS OF THE WORK, NOT OF THE IMPLEMENTATION. Operations:
the matrix products of the chunked (state-space dual) form AT THE
CONFIGURATION'S OWN `chunk_size` (128), a chunk of Q tokens: C B^T ONCE A
GROUP (2 Q^2 N x G), the masked product with X a head (2 Q^2 P x H), the
chunk's own state a head (2 Q P N x H), the carried part C h0 a head (2 Q
P N x H); the backward twice the forward (every product has two
gradients); nothing for what a lowering forms twice (a backward that forms
C B^T and the decays again, a product run at three bf16 passes). Bytes:
the least any lowering must move: x [T, H P], B, C [T, G N] and dt [T, H]
read and y written once a pass, the chunk-start states [T / Q, H, P, N]
float32 written once forward and read once backward, d y read and the four
gradients written backward. What a lowering moves or computes more shows
as a share below 100.

THE CONVOLUTION'S OP (`short_conv`, gating "silu", with a bias) is
bandwidth-bound: X [T, channels] read and Out written forward; X and d Out
read, d X written backward.

AN EXPERT IS TWO MATRICES: up and down and each one's two gradients, SIX
grouped products a layer and step where a gated expert makes nine.
"""

from chipbench.costs_lm import BF16, least_seconds, matmul_flops
from chipbench.costs_window_share import attention_bytes, attention_flops
from chipbench.reference.nemotron_3_nano_30b_a3b import (
    ATTENTION, EXPERTS, MAMBA, layer_kinds as layers, mamba_dims)

F32 = 4
EXPERT_PRODUCTS = 2          # up, down: no gate


def tokens(cfg):
    return cfg["rows_per_step"] * cfg["sequence_length"]


def chunks(cfg):
    """Chunks of `chunk_size` tokens a step's rows are."""
    return cfg["rows_per_step"] * -(-cfg["sequence_length"]
                                    // cfg["chunk_size"])


# --------------------------------------------------------------------- scan
def scan_flops_a_chunk(cfg):
    """Of one chunk, all heads, forward."""
    H, P, G, N, _, _ = mamba_dims(cfg)
    Q = cfg["chunk_size"]
    return (2 * Q * Q * N * G + 2 * Q * Q * P * H + 2 * Q * P * N * H
            + 2 * Q * P * N * H)


def scan_flops(cfg, train):
    return chunks(cfg) * scan_flops_a_chunk(cfg) * (3 if train else 1)


def scan_bytes(cfg, train):
    H, P, G, N, d, conv = mamba_dims(cfg)
    T = tokens(cfg)
    inputs = T * (conv + H) * BF16
    out = T * d * BF16
    states = chunks(cfg) * H * P * N * F32
    forward = inputs + out + states
    # the inputs and d y read, the four gradients written, the states read
    return forward + ((inputs + out + inputs + states) if train else 0)


def scan_least_seconds(cfg, train, peaks):
    """Of the op of ONE mixer over a step's rows."""
    return least_seconds(scan_flops(cfg, train), scan_bytes(cfg, train),
                         peaks)


def scan_least_seconds_of(cfg, train, peaks):
    return layers(cfg).count(MAMBA) * scan_least_seconds(cfg, train, peaks)


# ------------------------------------------------------------ short_conv op
def short_conv_flops(cfg, train):
    """L multiply-adds, the bias and a SiLU (4) an output element; the
    backward forms the convolution again, silu' (6), L multiply-adds for d
    X and L for d Filter."""
    n, L = tokens(cfg) * mamba_dims(cfg)[5], cfg["conv_kernel"]
    return (2 * L + 5) * n + ((6 * L + 7) * n if train else 0)


def short_conv_bytes(cfg, train, elem=BF16):
    n = tokens(cfg) * mamba_dims(cfg)[5] * elem
    return 2 * n + (3 * n if train else 0)


def short_conv_least_seconds_of(cfg, train, peaks):
    """Summed over every mixer the program runs."""
    return layers(cfg).count(MAMBA) * least_seconds(
        short_conv_flops(cfg, train), short_conv_bytes(cfg, train), peaks)


# --------------------------------------------------------------- attention
def attention_least_seconds_of(cfg, train, peaks):
    """Of the flash kernels of the attention layers over a step's rows: 32
    query heads on 2 key/value heads of 128, the whole triangle."""
    rows, seq = cfg["rows_per_step"], cfg["sequence_length"]
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    return layers(cfg).count(ATTENTION) * least_seconds(
        attention_flops(rows, heads, seq, d, None, train),
        attention_bytes(rows, heads, cfg["num_key_value_heads"], seq, d,
                        train), peaks)


# ------------------------------------------------------------ expert layer
def expert_layers(cfg):
    return layers(cfg).count(EXPERTS)


def grouped_kernels_per_step(cfg):
    """Grouped-matmul Pallas calls a training step makes: six an expert
    layer (up, down and each one's two gradients)."""
    return 3 * EXPERT_PRODUCTS * expert_layers(cfg)


def expert_layer_least_seconds(cfg, rows_held, train, peaks):
    """Of the grouped products of ONE layer over the rows the held experts
    received: up, down, in training each one's two gradients; each the
    larger of its operations and its bytes (the rows in, the held experts'
    matrices, the rows out)."""
    C, F, E = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["n_routed_experts"])
    one = least_seconds(
        matmul_flops(rows_held, C, F),
        (rows_held * C + E * C * F + rows_held * F) * BF16, peaks)
    return (3 if train else 1) * EXPERT_PRODUCTS * one


# ------------------------------------------------------------- whole model
def forward_flops_per_token(cfg, seq, rows_held_per_token):
    """Operations one token's forward pass needs, by part (norms, softmax,
    SiLU, softplus, the decays, top-k and the optimizer are left out, so a
    utilization built on this is slightly low, never high; the scan's
    matrix products at the stated chunk and the convolution's few
    operations an element ARE counted: they are the mixer's own).
    `rows_held_per_token`: rows the held experts of a layer received over
    the tokens of the step (top_k x held / all if routing is even)."""
    C, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    H, _, _, _, inner, conv = mamba_dims(cfg)
    shared = cfg["moe_shared_expert_intermediate_size"] \
        * cfg["n_shared_experts"]
    parts = dict.fromkeys(("mamba_projections", "short_conv", "scan",
                           "attention_projections", "attention", "router",
                           "held_experts", "shared_expert"), 0)
    for kind in layers(cfg):
        if kind == MAMBA:
            parts["mamba_projections"] += (
                matmul_flops(1, C, inner + conv + H)
                + matmul_flops(1, inner, C))
            parts["short_conv"] += (2 * cfg["conv_kernel"] + 5) * conv
            parts["scan"] += scan_flops_a_chunk(cfg) / cfg["chunk_size"]
        elif kind == ATTENTION:
            parts["attention_projections"] += (
                matmul_flops(1, C, heads * d) + 2 * matmul_flops(1, C, kv * d)
                + matmul_flops(1, heads * d, C))
            parts["attention"] += attention_flops(
                1, heads, seq, d, None, False) // seq
        else:
            parts["router"] += matmul_flops(
                1, C, cfg["deployment"]["n_routed_experts"])
            parts["held_experts"] += rows_held_per_token * EXPERT_PRODUCTS \
                * matmul_flops(1, C, cfg["moe_intermediate_size"])
            parts["shared_expert"] += EXPERT_PRODUCTS * matmul_flops(
                1, C, shared)
    parts["head"] = matmul_flops(1, C, cfg["vocab_size"])
    return parts


def train_flops_per_token(cfg, seq, rows_held_per_token):
    """Forward + backward (every product has two gradients): 3 x forward."""
    return 3 * sum(forward_flops_per_token(cfg, seq,
                                           rows_held_per_token).values())
