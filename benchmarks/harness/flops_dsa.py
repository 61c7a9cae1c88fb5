"""The benchmark's own count of the operations of a decoder whose attention
reads the keys an indexer selects (DeepSeek sparse attention over
grouped-query heads, ``configs/keye_vl2_30b_a3b.json``), from the
configuration's keys alone: what ``dsa_train_mfu_pct`` divides by the peak.

Counted, as 2 x multiply-accumulates of the forward pass: the four attention
matrices (q, k, v, o by the head counts and ``head_dim``), the indexer's
three (``indexer_num_heads x indexer_head_dim`` queries, one key of
``indexer_head_dim``, one weight a head), the index scores ``2 x
indexer_num_heads x indexer_head_dim`` a pair over ALL causal pairs ``T (T +
1) / 2`` (the indexer scores every earlier key to choose among them; the
1/16 of them that belong to rows with no more than ``topk`` keys, where
nothing is chosen, are in the count as the published indexer makes them, and
the program does not make them), the attention's scores ``4 heads head_dim``
a pair (QK^T and PV) over the pairs the selection leaves open, ``sum_t min(t
+ 1, topk)``, the router over all routed experts, the routed experts at the
share of a token's choices that a uniform routing sends to the experts held
here, and the head over the vocabulary held at the positions that carry
loss. Not counted: embedding look-ups, norms (the per-head ones too),
softmax, rotary embedding, the indexer's ReLU and weighted sum, the
selection itself (comparisons, no multiply-accumulate), pairs that are
computed and then closed by the mask, and anything recomputed in the
backward pass. The backward pass is counted as twice the forward, the
indexer's too, though no gradient passes through it: 3 x forward is the
convention every decoder cell here divides by, and the indexer is under 12%
of the count.
"""

from __future__ import annotations


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def open_pairs(seq_len: int, topk: int) -> int:
    """sum over queries ``t`` of the keys open to it: ``min(t + 1, topk)``."""
    if topk >= seq_len:
        return causal_pairs(seq_len)
    return causal_pairs(topk) + (seq_len - topk) * topk


def layer_forward_flops(cfg: dict, i: int, seq_len: int) -> dict:
    """One sequence through decoder layer ``i``, by part."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    d_attn = cfg["num_attention_heads"] * hd
    d_kv = cfg["num_key_value_heads"] * hd
    sa = cfg["sa_config"]
    d_index = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    index_matrices = d * (d_index + sa["indexer_num_kv_heads"]
                          * sa["indexer_head_dim"] + sa["indexer_num_heads"])
    dep = cfg["deployment"]
    routed, held = dep["num_experts_routed"], dep["held"][1]
    return {
        "projections": 2.0 * seq_len * d * (2 * d_attn + 2 * d_kv),
        "index_projections": 2.0 * seq_len * index_matrices,
        "index_scores": 2.0 * d_index * causal_pairs(seq_len),
        "scores": 4.0 * d_attn * open_pairs(seq_len, sa["topk"]),
        "router": 2.0 * seq_len * d * routed,
        "experts": 6.0 * seq_len * d * cfg["moe_intermediate_size"]
        * cfg["num_experts_per_tok"] * held / routed,
    }


def forward_flops(cfg: dict, seq_len: int) -> float:
    """Forward operations of one sequence of ``seq_len`` tokens."""
    total = sum(sum(layer_forward_flops(cfg, i, seq_len).values())
                for i in range(cfg["num_hidden_layers"]))
    return total + 2.0 * (seq_len - 1) * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    """Forward + backward = 3 x forward, recomputation not counted."""
    return 3.0 * forward_flops(cfg, traffic["seq_len"])
