"""Looped decoder stacks at Ouro's sizes (ByteDance Ouro-2.6B, ``model_type:
ouro``, https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json;
arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language Models"): a
dense decoder whose ``num_hidden_layers`` layers are walked
``total_ut_steps`` times with one set of weights, the final norm closing
every pass, and a head that scores every pass and weighs the passes by a
learned exit distribution.

``ouro(config, ...)`` lays the stack out from the published keys over
``models/decoder_stack.py`` with ``post_norms`` (a norm before and after
each sub-layer: eight vertices a layer) and ``passes=total_ut_steps``: the
layers and the final norm are ONE repeated run of the graph, every weight
one leaf. Attention is rotary full causal attention, the feed-forward gated
silu. The config does not carry the objective: ``exit_beta`` weighs the
entropy of the exit distribution (``ExitWeightedLMOutputLayer``).
``output()`` walks every pass and returns the last one's probabilities
(``early_exit_threshold`` 1); stopping early is not built.
"""

from __future__ import annotations

from typing import Optional

from ..nn.graph import ComputationGraph
from ..nn.layers.decoder import (CausalSelfAttentionLayer,
                                 ExitWeightedLMOutputLayer, GatedDenseLayer)
from .decoder_stack import decoder_stack

# what the builder does not build: a configuration that asks for it is
# refused, not approximated
_REQUIRED = {"sliding_window": None, "use_sliding_window": False,
             "rope_scaling": None, "tie_word_embeddings": False,
             "hidden_act": "silu", "early_exit_threshold": 1}


def ouro(config: dict, seq_len: int, *, exit_beta: float = 0.1, updater=None,
         dtype: str = "FLOAT", workspace_mode: Optional[str] = None,
         seed: int = 0) -> ComputationGraph:
    """The stack of ``config`` (the keys of the model's ``config.json``) for
    sequences of ``seq_len`` token ids, not yet initialised."""
    for key, want in _REQUIRED.items():
        if key in config and config[key] != want:
            raise NotImplementedError(
                f"{key}={config[key]!r}: this builder lays out "
                f"{key}={want!r} only")
    heads, kv_heads = config["num_attention_heads"], \
        config["num_key_value_heads"]
    if heads % kv_heads:
        raise NotImplementedError(
            f"num_key_value_heads={kv_heads} does not divide "
            f"num_attention_heads={heads}")

    def attention(i):
        return CausalSelfAttentionLayer(
            n_heads=heads, n_kv_heads=kv_heads,
            head_size=config["head_dim"],
            rope_theta=float(config["rope_theta"]))

    return decoder_stack(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        n_layers=config["num_hidden_layers"], eps=config["rms_norm_eps"],
        attention=attention,
        mlp=lambda i: GatedDenseLayer(n_hidden=config["intermediate_size"]),
        seq_len=seq_len, updater=updater, dtype=dtype,
        workspace_mode=workspace_mode, seed=seed,
        passes=config["total_ut_steps"], post_norms=True,
        head=ExitWeightedLMOutputLayer(n_out=config["vocab_size"],
                                       beta=exit_beta))
