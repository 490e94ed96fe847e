"""% of the causal (query, key) pairs the selections keep a step: the
static counters `sparse_attention_selected_pairs` /
`sparse_attention_causal_pairs` of the program the window times
(`paddle_tpu.ops.lm_ops.lowered_counts`: sum_t min(t + 1, topk) against S
(S + 1) / 2 a row and layer; 43.7 at rows of 8192 with topk 2048). None
where the program has no `indexer_select` op."""


def read(obs):
    pairs = obs.get("selected_pairs") or {}
    if not pairs.get("selected") or not pairs.get("causal"):
        return None
    return 100.0 * pairs["selected"] / pairs["causal"]
