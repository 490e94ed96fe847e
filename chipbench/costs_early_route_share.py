"""Operations and bytes of the matmul-shaped work of a decoder language
model that holds ONE CHIP'S SHARE of each layer, every layer of which is
sparse with ReLU-gated experts, and whose layers alternate between full
causal attention and a sliding window (`smallthinker_21b_a3b`), as
functions of the configuration's shapes and of the rows the held experts
really received. The band and the grouped-query bytes are
`costs_window_share`'s (`attention_flops`, `attention_bytes`: a band is
counted as a band, K and V read once a key/value head), the generic
pieces `costs_lm`'s; what is this configuration's is which layer is of
which kind (`sliding_window_layout`), that there is no dense layer, no
shared expert and no gate, and that the experts' epilogues hold no
transcendental (which changes no count here: element-wise work is never
counted). Nothing recomputed is counted: a least time built on these is
never too high, so a roofline share built on it is never too good.
"""

from chipbench.costs_lm import BF16, least_seconds, matmul_flops
from chipbench.costs_window_share import attention_bytes, attention_flops

FULL, WINDOW = "full", "window"


def layers(cfg):
    """The attention kind of each layer the program runs."""
    return [WINDOW if w else FULL for w in
            cfg["sliding_window_layout"][:cfg["num_hidden_layers"]]]


def sparse_layers(cfg):
    return cfg["num_hidden_layers"]


# --------------------------------------------------------------- attention
def _window(cfg, kind):
    return cfg["sliding_window_size"] if kind == WINDOW else None


def attention_least_seconds(cfg, kind, train, peaks):
    """Of the flash kernels of ONE layer of `kind` over a step's rows."""
    rows, seq, d = (cfg["rows_per_step"], cfg["sequence_length"],
                    cfg["head_dim"])
    heads = cfg["num_attention_heads"]
    return least_seconds(
        attention_flops(rows, heads, seq, d, _window(cfg, kind), train),
        attention_bytes(rows, heads, cfg["num_key_value_heads"], seq, d,
                        train), peaks)


def attention_least_seconds_of(cfg, kind, train, peaks):
    """Summed over every layer of `kind` the program runs."""
    return layers(cfg).count(kind) * attention_least_seconds(
        cfg, kind, train, peaks)


# ------------------------------------------------------------ expert layer
def grouped_kernels_per_step(cfg):
    """Grouped-matmul Pallas calls a training step makes: nine a layer."""
    return 9 * sparse_layers(cfg)


def expert_layer_least_seconds(cfg, rows_held, train, peaks):
    """Of the grouped products of ONE layer over the rows the held experts
    received: gate, up, down, in training each one's two gradients; each
    the larger of its operations and its bytes (the rows in, the held
    experts' matrices, the rows out)."""
    C, F, E = (cfg["hidden_size"], cfg["moe_ffn_hidden_size"],
               cfg["moe_num_primary_experts"])
    one = least_seconds(
        matmul_flops(rows_held, C, F),
        (rows_held * C + E * C * F + rows_held * F) * BF16, peaks)
    return (9 if train else 3) * one


# ------------------------------------------------------------- whole model
def forward_flops_per_token(cfg, seq, rows_held_per_token):
    """Operations one token's forward pass needs, by part (norms, rotary,
    softmax, ReLU, top-k and the optimizer are left out, so a utilization
    built on this is slightly low, never high). `rows_held_per_token`: rows
    the held experts of a layer received over the tokens of the step
    (top_k x held / all if routing is even)."""
    C, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    parts = dict.fromkeys(("projections", "attention_full",
                           "attention_window", "router", "held_experts"), 0)
    for kind in layers(cfg):
        parts["projections"] += (
            matmul_flops(1, C, heads * d) + 2 * matmul_flops(1, C, kv * d)
            + matmul_flops(1, heads * d, C))
        parts["attention_" + kind] += attention_flops(
            1, heads, seq, d, _window(cfg, kind), False) // seq
        parts["router"] += matmul_flops(
            1, C, cfg["deployment"]["moe_num_primary_experts"])
        parts["held_experts"] += rows_held_per_token * 3 * matmul_flops(
            1, C, cfg["moe_ffn_hidden_size"])
    parts["head"] = matmul_flops(1, C, cfg["vocab_size"])
    return parts


def train_flops_per_token(cfg, seq, rows_held_per_token):
    """Forward + backward (every product has two gradients): 3 x forward."""
    return 3 * sum(forward_flops_per_token(cfg, seq,
                                           rows_held_per_token).values())
