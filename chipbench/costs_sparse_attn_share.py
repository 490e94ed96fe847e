"""Operations and bytes of the work of a decoder language model that holds
ONE CHIP'S SHARE of the experts and of the vocabulary and whose every layer
is grouped-query attention OVER THE KEYS A LEARNED INDEXER PICKS for each
query (`keye_vl_2_0_30b_a3b`), as functions of the configuration's shapes
and of the rows the held experts really received. The generic pieces are
`costs_lm`'s.

BOTH COUNTS ARE OF THE WORK, NOT OF THE IMPLEMENTATION.

* ATTENTION counts the CHOSEN pairs: a query t attends to min(t + 1, topk)
  keys, sum_t min(t + 1, topk) pairs a row (14,681,088 at 8192 with topk
  2048: 43.7% of the triangle's 33,558,528). Forward Q K^T and P V (4 D
  operations a pair and head); the backward's five products (the scores
  again, dV, dP, dQ, dK: 10 D). Bytes: q, k, v read and o written forward;
  q, k, v, o, d o read and the three gradients written backward (K and V a
  key/value head each, once for its whole group). A lowering that computes
  the whole triangle and masks reads at most 43.7% x its kernel's
  efficiency here, and a later change that skips unchosen blocks has a
  yardstick that does not move.
* THE INDEXER counts EVERY CAUSAL pair (the selection has to look at each):
  2 x Hi x Di operations a pair forward, and its two gradients (d q_I, d
  k_I) as many each in training. Bytes: q_I, k_I, w read and the mask [S,
  S] int8 written forward; q_I, k_I, w read and their gradients written
  backward. What a lowering adds (forming the scores again for the loss,
  the head-mean probabilities, the bisection's passes) shows as a share
  below 100.
"""

# the expert layer's counts are the Qwen3-Next cell's: the same share path,
# the same keys (`hidden_size`, `moe_intermediate_size`, `num_experts` held)
from chipbench.costs_delta_share import (  # noqa: F401
    expert_layer_least_seconds, grouped_kernels_per_step)
from chipbench.costs_lm import BF16, least_seconds, matmul_flops

F32 = 4


def tokens(cfg):
    return cfg["rows_per_step"] * cfg["sequence_length"]


def selected_pairs(seq, topk):
    """sum_t min(t + 1, topk) over a row of `seq` tokens."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def _indexer(cfg):
    sa = cfg["sa_config"]
    return sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]


# --------------------------------------------------------------- attention
def sparse_attention_flops(cfg, train):
    """Of ONE layer over a step's rows: the chosen pairs."""
    pairs = cfg["rows_per_step"] * selected_pairs(cfg["sequence_length"],
                                                  _indexer(cfg)[2])
    per_pair = 2 * cfg["head_dim"] * (7 if train else 2)
    return cfg["num_attention_heads"] * pairs * per_pair


def sparse_attention_bytes(cfg, train, elem=BF16):
    tensor = tokens(cfg) * cfg["head_dim"] * elem
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    forward = (2 * heads + 2 * kv) * tensor
    return forward + ((4 * heads + 4 * kv) * tensor if train else 0)


def sparse_attention_least_seconds_of(cfg, train, peaks):
    """Summed over every layer the program runs."""
    return cfg["num_hidden_layers"] * least_seconds(
        sparse_attention_flops(cfg, train),
        sparse_attention_bytes(cfg, train), peaks)


# ----------------------------------------------------------------- indexer
def indexer_flops(cfg, train):
    """Of ONE layer over a step's rows: every causal pair."""
    hi, di, _ = _indexer(cfg)
    pairs = cfg["rows_per_step"] * causal_pairs(cfg["sequence_length"])
    return pairs * 2 * hi * di * (3 if train else 1)


def indexer_bytes(cfg, train, elem=BF16):
    hi, di, _ = _indexer(cfg)
    inputs = tokens(cfg) * (hi * di + di + hi) * elem
    mask = cfg["rows_per_step"] * cfg["sequence_length"] ** 2
    return inputs + mask + (2 * inputs if train else 0)


def indexer_least_seconds_of(cfg, train, peaks):
    return cfg["num_hidden_layers"] * least_seconds(
        indexer_flops(cfg, train), indexer_bytes(cfg, train), peaks)


# ------------------------------------------------------------- whole model
def forward_flops_per_token(cfg, seq, rows_held_per_token):
    """Operations one token's forward pass needs, by part (norms, rotary,
    softmax, SiLU, the bisection, top-k of the experts and the optimizer
    are left out, so a utilization built on this is slightly low, never
    high): the attention's projections, the indexer's projections and its
    scores over the causal pairs, attention over the CHOSEN pairs, the
    router over all experts, the held experts over the rows they really
    received, the head."""
    C, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hi, di, topk = _indexer(cfg)
    layers = cfg["num_hidden_layers"]
    parts = {
        "attention_projections": 2 * matmul_flops(1, C, heads * d)
        + 2 * matmul_flops(1, C, kv * d),
        "indexer_projections": matmul_flops(1, C, hi * di)
        + matmul_flops(1, C, di) + matmul_flops(1, C, hi),
        "indexer_scores": 2 * hi * di * causal_pairs(seq) / seq,
        "attention": heads * 4 * d * selected_pairs(seq, topk) / seq,
        "router": matmul_flops(1, C, cfg["deployment"]["num_experts"]),
        "held_experts": rows_held_per_token * 3 * matmul_flops(
            1, C, cfg["moe_intermediate_size"])}
    parts = {k: v * layers for k, v in parts.items()}
    parts["head"] = matmul_flops(1, C, cfg["vocab_size"])
    return parts


def train_flops_per_token(cfg, seq, rows_held_per_token):
    """Forward + backward. Every product has two gradients (3 x forward)
    but attention's, whose backward is five products to the forward's two
    (the scores are formed again: 3.5 x), as `sparse_attention_flops`
    counts it."""
    parts = forward_flops_per_token(cfg, seq, rows_held_per_token)
    return 3 * sum(parts.values()) + 0.5 * parts["attention"]
