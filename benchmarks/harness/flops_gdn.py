"""The benchmark's own count of a Qwen3-Next-style decoder's operations
(``configs/qwen3_next_80b_a3b.json``), from the configuration's keys alone:
what ``gdn_train_mfu_pct`` divides by the peak, and the cost of the
delta-rule kernels that ``delta_rule_roofline`` reads.

Counted as 2 x multiply-accumulates of the forward pass. A Gated DeltaNet
layer: its three projections (``in_proj_qkvz``, ``in_proj_ba``,
``out_proj``), the depthwise convolution, and the chunked recurrence a
value head and a position (chunk ``C``, key and value widths ``dk``,
``dv``): ``4 C dk`` for ``K K^T`` and ``Q K^T`` inside the chunk, ``C (dk +
dv)`` for the triangular solve's ``C^2 / 2`` multiply-adds a right-hand
column, ``6 dk dv`` for the three products with the state (``W S``, ``(A Q)
S``, ``Kd^T U``) and ``2 C dv`` for ``P U``. The gated full layer goes
through ``flops_lm``'s formula, with the output gate's width. Every layer's
experts as ``flops_lm`` counts them (router over all routed experts, the
held experts at a uniform routing's share, the shared expert) and the shared
expert's gate. The head over the vocabulary held at the positions that carry
loss. Not counted: embedding look-ups, norms, activations, the decay and
``beta``, the L2 norms, softmax, rotary embedding, and anything recomputed.
"""

from __future__ import annotations

from . import flops_lm


#: positions a chunk of the program's delta rule holds
#: (``ops/delta_rule.py`` ``CHUNK``)
CHUNK = 64


def is_full(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def _lm_view(cfg: dict) -> dict:
    """The keys ``flops_lm`` reads, from the published ones: every layer
    sparse, the full layers gated, no window."""
    n = cfg["num_hidden_layers"]
    return dict(cfg, gating=True,
                num_attention_heads_per_layer=[cfg["num_attention_heads"]] * n,
                layer_types=["full_attention"] * n,
                mlp_layer_types=["sparse"] * n, sliding_window=None)


def recurrence_flops_per_token(cfg: dict) -> float:
    """The chunked gated delta rule, one position of every value head."""
    C, dk, dv = CHUNK, cfg["linear_key_head_dim"], \
        cfg["linear_value_head_dim"]
    return float(cfg["linear_num_value_heads"]) * (
        4 * C * dk + C * (dk + dv) + 6 * dk * dv + 2 * C * dv)


def layer_forward_flops(cfg: dict, i: int, seq_len: int) -> dict:
    """One sequence through decoder layer ``i``, by part."""
    lm = flops_lm.layer_forward_flops(_lm_view(cfg), i, seq_len)
    experts = {k: lm[k] for k in ("router", "shared", "experts")}
    experts["shared_gate"] = 2.0 * seq_len * cfg["hidden_size"]
    if is_full(cfg, i):
        return dict(experts, projections=lm["projections"],
                    scores=lm["scores"])
    d = cfg["hidden_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    channels = 2 * hk * dk + hv * dv
    return dict(
        experts,
        projections=2.0 * seq_len * d * (2 * hk * dk + 2 * hv * dv + 2 * hv
                                         + hv * dv),
        conv=2.0 * seq_len * channels * cfg["linear_conv_kernel_dim"],
        recurrence=seq_len * recurrence_flops_per_token(cfg))


def forward_flops(cfg: dict, seq_len: int) -> float:
    """Forward operations of one sequence of ``seq_len`` tokens."""
    total = sum(sum(layer_forward_flops(cfg, i, seq_len).values())
                for i in range(cfg["num_hidden_layers"]))
    return total + 2.0 * (seq_len - 1) * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    """Forward + backward = 3 x forward, recomputation not counted."""
    return 3.0 * forward_flops(cfg, traffic["seq_len"])


def delta_rule_cost(kind: str, rows: int, tokens: int, chunk_size: int,
                    dk: int, dv: int, nbytes: float):
    """One delta-rule kernel over ``rows`` heads of ``tokens`` positions in
    chunks of ``chunk_size``: ``delta_rule_fwd`` (``W S``, ``(A Q) S``,
    ``Kd^T U``: ``6 dk dv``, and ``P U``: ``2 C dv`` a position),
    ``delta_rule_states`` (``W S`` and ``Kd^T U``: ``4 dk dv``) or
    ``delta_rule_bwd`` (seven products of ``[C, dk] x [dk, dv]``'s size:
    ``14 dk dv``, and two of ``P``'s: ``4 C dv``). ``nbytes``: what the call
    reads and writes, every operand and result once. -> (operations,
    bytes)."""
    per = {"fwd": 6 * dk * dv + 2 * chunk_size * dv,
           "states": 4 * dk * dv,
           "bwd": 14 * dk * dv + 4 * chunk_size * dv}[kind]
    return float(rows) * tokens * per, float(nbytes)
