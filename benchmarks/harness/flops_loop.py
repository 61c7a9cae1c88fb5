"""The benchmark's own count of a looped decoder's operations, from the
configuration's keys alone (``configs/ouro_2_6b.json``): what
``loop_train_mfu_pct`` divides by the peak. ``flops_lm`` walks each layer
once and scores one hidden state; here ``total_ut_steps`` passes walk the
same layers and every pass is scored.

Counted, as 2 x multiply-accumulates of the forward pass, for each of the
``total_ut_steps`` passes: a layer's four attention matrices (``Wq`` and
``Wo`` hidden x heads x head_dim, ``Wk`` and ``Wv`` hidden x kv_heads x
head_dim), its causal scores ``2 heads (2 head_dim) T (T + 1) / 2`` (QK^T
and PV over the pairs the mask leaves open), its gated feed-forward (three
matrices hidden x intermediate); then the head over the whole vocabulary and
the exit gate (hidden x 1) at the positions that carry loss. Not counted:
embedding look-ups, the norms (five a layer application), softmax, rotary
embedding, the exit distribution, and anything recomputed in the backward
pass.
"""

from __future__ import annotations


def layer_forward_flops(cfg: dict, seq_len: int) -> dict:
    """One sequence through ONE application of a decoder layer, by part."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"projections": 2.0 * seq_len * (2 * d * heads * hd
                                            + 2 * d * kv * hd),
            "scores": 2.0 * heads * 2 * hd * (seq_len * (seq_len + 1) // 2),
            "feed_forward": 6.0 * seq_len * d * cfg["intermediate_size"]}


def head_forward_flops(cfg: dict, seq_len: int) -> dict:
    """One sequence's ONE pass through the head and the gate."""
    scored = seq_len - 1
    return {"head": 2.0 * scored * cfg["hidden_size"] * cfg["vocab_size"],
            "gate": 2.0 * scored * cfg["hidden_size"]}


def forward_flops(cfg: dict, seq_len: int) -> float:
    """Forward operations of one sequence of ``seq_len`` tokens: every pass
    walks every layer and is scored."""
    one_pass = cfg["num_hidden_layers"] * sum(
        layer_forward_flops(cfg, seq_len).values()) \
        + sum(head_forward_flops(cfg, seq_len).values())
    return cfg["total_ut_steps"] * one_pass


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    """Forward + backward = 3 x forward, recomputation not counted."""
    return 3.0 * forward_flops(cfg, traffic["seq_len"])
