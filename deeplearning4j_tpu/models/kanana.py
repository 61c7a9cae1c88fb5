"""DeepSeek-V3-style decoder stacks at kanana-2's sizes (kakaocorp
kanana-2-30b-a3b-instruct-2601, ``model_type: deepseek_v3``,
https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json):
latent attention in every layer, ``first_k_dense_replace`` leading dense
feed-forwards, then sparse experts chosen on biased sigmoid scores
(``topk_method: noaux_tc``) and weighed by the unbiased ones, with
``n_shared_experts`` shared experts run as one gated feed-forward.

``kanana2(config, ...)`` lays the stack out from the published keys over the
six-vertex layer layout of ``models/decoder_stack.py``. ``held=(first,
count)`` tells every sparse layer which of ``n_routed_experts`` experts it
holds; the router keeps its width and its bias. The selection bias is layer
state, zeros after ``init()``: a checkpoint's ``e_score_correction_bias``
goes into ``net.state["l<i>.mlp"]["select_bias"]``. Training leaves it as it
is (the balancing rule that moves it from load counts is not built).
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..nn.graph import ComputationGraph
from ..nn.layers.decoder import (GatedDenseLayer, LatentAttentionLayer,
                                 SparseExpertLayer)
from .decoder_stack import decoder_stack

# what the builder does not build: a configuration that asks for it is
# refused, not approximated
_REQUIRED = {"q_lora_rank": None, "rope_scaling": None, "n_group": 1,
             "topk_group": 1, "scoring_func": "sigmoid",
             "topk_method": "noaux_tc", "norm_topk_prob": True,
             "rope_interleave": True, "moe_layer_freq": 1,
             "hidden_act": "silu", "attention_bias": False}


def kanana2(config: dict, seq_len: int, *,
            held: Optional[Tuple[int, int]] = None, updater=None,
            dtype: str = "FLOAT", workspace_mode: Optional[str] = None,
            seed: int = 0) -> ComputationGraph:
    """The stack of ``config`` (the keys of the model's ``config.json``) for
    sequences of ``seq_len`` token ids, not yet initialised. ``held``: the
    experts every sparse layer holds (None: all)."""
    for key, want in _REQUIRED.items():
        if key in config and config[key] != want:
            raise NotImplementedError(
                f"{key}={config[key]!r}: this builder lays out "
                f"{key}={want!r} only")

    def attention(i):
        return LatentAttentionLayer(
            n_heads=config["num_attention_heads"],
            nope_head_size=config["qk_nope_head_dim"],
            rope_head_size=config["qk_rope_head_dim"],
            v_head_size=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
            rope_theta=float(config["rope_theta"]),
            eps=config["rms_norm_eps"])

    def mlp(i):
        if i < config["first_k_dense_replace"]:
            return GatedDenseLayer(n_hidden=config["intermediate_size"])
        width = config["moe_intermediate_size"]
        return SparseExpertLayer(
            num_experts=config["n_routed_experts"],
            top_k=config["num_experts_per_tok"], n_hidden=width,
            shared_hidden=config["n_shared_experts"] * width, held=held,
            routed_scale=config["routed_scaling_factor"], select_bias=True)

    return decoder_stack(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        n_layers=config["num_hidden_layers"], eps=config["rms_norm_eps"],
        attention=attention, mlp=mlp, seq_len=seq_len, updater=updater,
        dtype=dtype, workspace_mode=workspace_mode, seed=seed)
