"""The benchmark's own count of a causal decoder's operations, from the
configuration's keys alone (``configs/laguna_xs2.json``): what
``lm_train_mfu_pct`` divides by the peak.

Counted, as 2 x multiply-accumulates of the forward pass: the projections by
each layer's own head count (q, k, v, the output gate, o), the causal scores
``4 d_attn sum_i min(i + 1, W_l)`` (QK^T and PV over the pairs the mask
leaves open: all earlier keys in a full layer, the last ``W_l`` in a window
layer), the dense and shared feed-forwards, the router over all routed
experts, the routed experts at the share of a token's choices that a
uniform routing sends to the experts held here, and the head over the
vocabulary held at the positions that carry loss. Not counted: embedding
look-ups, norms, softmax, rotary embedding, the gate's sigmoid, and anything
recomputed in the backward pass.
"""

from __future__ import annotations


def open_pairs(seq_len: int, window=None) -> int:
    """sum over queries ``i`` of the keys open to it: ``min(i + 1, W)``."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def layer_forward_flops(cfg: dict, i: int, seq_len: int) -> dict:
    """One sequence through decoder layer ``i``, by part."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    d_attn = cfg["num_attention_heads_per_layer"][i] * hd
    d_kv = cfg["num_key_value_heads"] * hd
    gate = d_attn if cfg.get("gating") else 0
    window = cfg["sliding_window"] \
        if cfg["layer_types"][i] == "sliding_attention" else None
    out = {"projections": 2.0 * seq_len * d * (2 * d_attn + 2 * d_kv + gate),
           "scores": 4.0 * d_attn * open_pairs(seq_len, window)}
    if cfg["mlp_layer_types"][i] == "dense":
        out["dense"] = 6.0 * seq_len * d * cfg["intermediate_size"]
    else:
        dep = cfg["deployment"]
        routed, held = dep["num_experts_routed"], dep["held"][1]
        out["router"] = 2.0 * seq_len * d * routed
        out["shared"] = 6.0 * seq_len * d \
            * cfg["shared_expert_intermediate_size"]
        out["experts"] = 6.0 * seq_len * d * cfg["moe_intermediate_size"] \
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
