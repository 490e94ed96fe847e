"""% of its roofline attention over the selection reached, forward and
backward, over the layers of the window's steps: the least seconds of the
WORK (`costs_sparse_attn_share.sparse_attention_least_seconds_of`: the
CHOSEN pairs, sum_t min(t + 1, topk) a row, 4 D operations a pair and head
forward and the backward's five products, against q, k, v, o, d o and the
gradients moved once, at `peaks.json`; counted the same whatever lowers
the op) over the seconds of the operations under the op's two scopes. A
lowering that computes the whole triangle and masks reads at most the
chosen share (43.7% at 8192 with topk 2048) of its kernels' efficiency.
None unless both scopes are in the trace."""

from chipbench import costs_sparse_attn_share as costs
from chipbench import scopes


def read(obs):
    red, steps = obs.get("scopes"), obs.get("steps_in_window")
    if not red or not steps:
        return None
    fwd = sum(s for k, s in red["by_scope"].items()
              if scopes.in_scope(k, "sparse_attention")
              and not scopes.in_scope(k, "sparse_attention_grad"))
    bwd = scopes.seconds(red, "sparse_attention_grad")
    if not fwd or not bwd:
        return None
    least = costs.sparse_attention_least_seconds_of(obs["cfg"], True,
                                                    obs["peaks"])
    return 100.0 * least * steps / (fwd + bwd)
