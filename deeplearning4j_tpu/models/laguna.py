"""Laguna-style decoder stacks (poolside Laguna-XS.2,
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json): full and
sliding-window attention layers of different head counts in one stack, a
leading dense feed-forward, then sparse experts with a shared expert.

``laguna(config, ...)`` lays the stack out from the published keys
``layer_types``, ``mlp_layer_types`` and ``num_attention_heads_per_layer``
as a :class:`ComputationGraph` on token ids (the six-vertex layer layout of
``models/decoder_stack.py``, so ``workspace_mode="every_6"`` recomputes one
decoder layer at a time in the backward pass). ``held=(first, count)`` tells
every sparse layer which of ``num_experts`` experts it holds (one chip's
share of a layer that is divided over several); the router keeps its width.
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..nn.graph import ComputationGraph
from ..nn.layers.decoder import (CausalSelfAttentionLayer, GatedDenseLayer,
                                 SparseExpertLayer)
from .decoder_stack import VERTICES_PER_LAYER, decoder_stack  # noqa: F401


def attention_layer(config: dict, i: int) -> CausalSelfAttentionLayer:
    """Layer ``i``'s attention from the published keys: its own head count,
    and the rotary settings and mask of its kind."""
    kind = config["layer_types"][i]
    rope = config["rope_parameters"][kind]
    head = config["head_dim"]
    return CausalSelfAttentionLayer(
        n_heads=config["num_attention_heads_per_layer"][i],
        n_kv_heads=config["num_key_value_heads"], head_size=head,
        window=config["sliding_window"] if kind == "sliding_attention"
        else None,
        gated=bool(config.get("gating")),
        rotary_dim=int(head * rope.get("partial_rotary_factor", 1)),
        rope_theta=float(rope["rope_theta"]), rope_type=rope["rope_type"],
        rope_factor=float(rope.get("factor", 1.0)),
        rope_original_max_position=int(
            rope.get("original_max_position_embeddings", 0)),
        rope_beta_fast=float(rope.get("beta_fast", 32.0)),
        rope_beta_slow=float(rope.get("beta_slow", 1.0)),
        rope_attention_factor=float(rope.get("attention_factor", 1.0)))


def laguna(config: dict, seq_len: int, *,
           held: Optional[Tuple[int, int]] = None, updater=None,
           dtype: str = "FLOAT", workspace_mode: Optional[str] = None,
           seed: int = 0) -> ComputationGraph:
    """The stack of ``config`` (the keys of the model's ``config.json``) for
    sequences of ``seq_len`` token ids, not yet initialised. ``held``: the
    experts every sparse layer holds (None: all)."""
    def mlp(i):
        if config["mlp_layer_types"][i] == "dense":
            return GatedDenseLayer(n_hidden=config["intermediate_size"])
        return SparseExpertLayer(
            num_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"],
            n_hidden=config["moe_intermediate_size"],
            shared_hidden=config.get("shared_expert_intermediate_size", 0),
            held=held,
            routed_scale=config.get("moe_routed_scaling_factor", 1.0))

    return decoder_stack(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        n_layers=config["num_hidden_layers"], eps=config["rms_norm_eps"],
        attention=lambda i: attention_layer(config, i), mlp=mlp,
        seq_len=seq_len, updater=updater, dtype=dtype,
        workspace_mode=workspace_mode, seed=seed)
