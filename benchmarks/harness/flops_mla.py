"""The benchmark's own count of a latent-attention (DeepSeek-V3 block)
decoder's operations, from the configuration's keys alone
(``configs/kanana2_30b_a3b.json``): what ``mla_train_mfu_pct`` divides by the
peak. ``flops_lm`` counts ``heads x head_dim`` projections and one width for
scores and values; this layer has four matrices of its own and scores over
192 channels beside values of 128.

Counted, as 2 x multiply-accumulates of the forward pass: the four attention
matrices (``Wq`` hidden x heads x (nope + rope), ``Wkva`` hidden x (rank +
rope), ``Wkvb`` rank x heads x (nope + v), ``Wo`` heads x v x hidden), the
causal scores ``2 heads (nope + rope + v) T (T + 1) / 2`` (QK^T over the
scored width and PV over the value width, over the pairs the mask leaves
open), the dense and shared feed-forwards, the router over all routed
experts, the routed experts at the share of a token's choices that a uniform
routing sends to the experts held here, and the head over the vocabulary
held at the positions that carry loss. Not counted: embedding look-ups,
norms (the latent's too), softmax, rotary embedding, and anything recomputed
in the backward pass.
"""

from __future__ import annotations


def layer_forward_flops(cfg: dict, i: int, seq_len: int) -> dict:
    """One sequence through decoder layer ``i``, by part."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    matrices = (d * heads * (nope + rope) + d * (rank + rope)
                + rank * heads * (nope + vd) + heads * vd * d)
    out = {"projections": 2.0 * seq_len * matrices,
           "scores": 2.0 * heads * (nope + rope + vd)
           * (seq_len * (seq_len + 1) // 2)}
    if i < cfg["first_k_dense_replace"]:
        out["dense"] = 6.0 * seq_len * d * cfg["intermediate_size"]
    else:
        dep = cfg["deployment"]
        routed, held = dep["num_experts_routed"], dep["held"][1]
        width = cfg["moe_intermediate_size"]
        out["router"] = 2.0 * seq_len * d * routed
        out["shared"] = 6.0 * seq_len * d * cfg["n_shared_experts"] * width
        out["experts"] = 6.0 * seq_len * d * width \
            * cfg["num_experts_per_tok"] * held / routed
    return out


def forward_flops(cfg: dict, seq_len: int) -> float:
    """Forward operations of one sequence of ``seq_len`` tokens."""
    total = sum(sum(layer_forward_flops(cfg, i, seq_len).values())
                for i in range(cfg["num_hidden_layers"]))
    return total + 2.0 * (seq_len - 1) * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    """Forward + backward = 3 x forward, recomputation not counted."""
    return 3.0 * forward_flops(cfg, traffic["seq_len"])
