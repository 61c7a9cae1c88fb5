"""Qwen3-Next decoder stacks (Qwen3-Next-80B-A3B-Instruct,
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json):
Gated DeltaNet linear-attention layers with a gated full-attention layer
among every few, and in every layer sparse SwiGLU experts behind a softmax
router beside one shared expert whose output a sigmoid gate scales.

``qwen3_next(config, ...)`` lays the stack out from the published keys over
the six-vertex layer layout of ``models/decoder_stack.py``: layer ``i`` is a
full-attention layer where ``(i + 1) % full_attention_interval == 0``
(``linear_*`` keys otherwise: ``GatedDeltaNetLayer``), and every layer's
feed-forward is sparse (``decoder_sparse_step`` 1, no ``mlp_only_layers``).
The full layer is ``CausalSelfAttentionLayer(gated=True, qk_norm=True)``
with the first ``partial_rotary_factor`` of each head rotated. The published
norms are zero-centred, ``(1 + w)`` with ``w`` from 0; without weight decay
that is the gain ``g`` from 1 that ``RMSNormLayer`` keeps, and Adam walks the
same path. The published query projection of the full layer interleaves each
head's query with its gate; here they are two matrices, ``Wq`` and ``Wg``,
with the same columns. ``held=(first, count)`` tells every expert layer which
of ``num_experts`` it holds and ``absent`` what stands in for the others in
a token's sum; the router keeps its width. The head makes its logits
``LM_HEAD_BLOCK`` positions at a time (a 32,768-position row's would be
2.5 GB of float32 at an eighth of the vocabulary).
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..nn.graph import ComputationGraph
from ..nn.layers.decoder import (LM_HEAD_BLOCK, CausalLMOutputLayer,
                                 CausalSelfAttentionLayer, GatedDeltaNetLayer,
                                 SparseExpertLayer)
from .decoder_stack import decoder_stack

# what the builder does not build: a configuration that asks for it is
# refused, not approximated
_REQUIRED = {"rope_scaling": None, "tie_word_embeddings": False,
             "use_sliding_window": False, "norm_topk_prob": True,
             "decoder_sparse_step": 1, "mlp_only_layers": [],
             "hidden_act": "silu"}


def is_full(config: dict, i: int) -> bool:
    """Whether layer ``i`` is a full-attention layer."""
    return (i + 1) % config["full_attention_interval"] == 0


def qwen3_next(config: dict, seq_len: int, *,
               held: Optional[Tuple[int, int]] = None,
               absent: str = "left_out", updater=None, dtype: str = "FLOAT",
               workspace_mode: Optional[str] = None,
               seed: int = 0) -> ComputationGraph:
    """The stack of ``config`` (the keys of the model's ``config.json``) for
    sequences of ``seq_len`` token ids, not yet initialised. ``held``: the
    experts every layer holds (None: all); ``absent``: what stands in for
    the chosen experts held elsewhere (``SparseExpertLayer.absent``)."""
    for key, want in _REQUIRED.items():
        if key in config and config[key] != want:
            raise NotImplementedError(
                f"{key}={config[key]!r}: this builder lays out "
                f"{key}={want!r} only")
    eps = config["rms_norm_eps"]

    def attention(i):
        if is_full(config, i):
            head = config["head_dim"]
            return CausalSelfAttentionLayer(
                n_heads=config["num_attention_heads"],
                n_kv_heads=config["num_key_value_heads"], head_size=head,
                gated=True, qk_norm=True, eps=eps,
                rotary_dim=int(head * config["partial_rotary_factor"]),
                rope_theta=float(config["rope_theta"]))
        return GatedDeltaNetLayer(
            n_key_heads=config["linear_num_key_heads"],
            n_value_heads=config["linear_num_value_heads"],
            key_size=config["linear_key_head_dim"],
            value_size=config["linear_value_head_dim"],
            conv_size=config["linear_conv_kernel_dim"], rms_eps=eps)

    def mlp(i):
        return SparseExpertLayer(
            num_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"],
            n_hidden=config["moe_intermediate_size"],
            shared_hidden=config["shared_expert_intermediate_size"],
            shared_gate=True, held=held, scoring="softmax", absent=absent)

    return decoder_stack(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        n_layers=config["num_hidden_layers"], eps=eps, attention=attention,
        mlp=mlp, seq_len=seq_len, updater=updater, dtype=dtype,
        workspace_mode=workspace_mode, seed=seed,
        head=CausalLMOutputLayer(n_out=config["vocab_size"],
                                 block=LM_HEAD_BLOCK))
