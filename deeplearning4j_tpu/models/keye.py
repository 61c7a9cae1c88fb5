"""Keye-VL-2.0's language model (Kwai-Keye Keye-VL-2.0-30B-A3B,
``model_type: KeyeVL2``,
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json):
grouped-query attention with per-head RMS norms on queries and keys whose
open keys a learned indexer chooses at run time (``sa_config``: 16 index
heads of 64 against one index key, the ``topk`` best-scored earlier keys a
query), and in every layer sparse experts behind a softmax router (the
``num_experts_per_tok`` largest probabilities renormalised over the chosen,
no shared expert).

``keye_vl2(config, ...)`` lays the stack out from the published keys over
the six-vertex layer layout of ``models/decoder_stack.py``. ``held=(first,
count)`` tells every sparse layer which of ``num_experts`` experts it holds;
the router keeps its width. The model is built on token ids: the vision
tower is not, and with text alone the three position streams of
``rope_scaling.mrope_section`` are one, so the rotation is the plain one over
the whole head. The indexer's three matrices are parameters that no gradient
reaches (``SparseSelectAttentionLayer``).
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..nn.graph import ComputationGraph
from ..nn.layers.decoder import (SparseExpertLayer,
                                 SparseSelectAttentionLayer)
from .decoder_stack import decoder_stack

# what the builder does not build: a configuration that asks for it is
# refused, not approximated
_REQUIRED = {"use_sliding_window": False, "sliding_window": None,
             "mlp_only_layers": [], "decoder_sparse_step": 1,
             "attention_bias": False, "tie_word_embeddings": False,
             "norm_topk_prob": True, "hidden_act": "silu"}


def keye_vl2(config: dict, seq_len: int, *,
             held: Optional[Tuple[int, int]] = None, updater=None,
             dtype: str = "FLOAT", workspace_mode: Optional[str] = None,
             seed: int = 0, image_inputs: bool = False) -> ComputationGraph:
    """The stack of ``config`` (the language model's keys of the model's
    ``config.json``) for sequences of ``seq_len`` token ids, not yet
    initialised. ``held``: the experts every sparse layer holds (None:
    all)."""
    if image_inputs:
        raise NotImplementedError(
            "image_inputs: the vision tower and the three position streams "
            "of mrope_section are not built; token ids only")
    for key, want in _REQUIRED.items():
        if key in config and config[key] != want:
            raise NotImplementedError(
                f"{key}={config[key]!r}: this builder lays out "
                f"{key}={want!r} only")
    rope = config.get("rope_scaling") or {}
    if rope.get("rope_type", rope.get("type", "default")) != "default":
        raise NotImplementedError(
            f"rope_scaling={rope!r}: plain rotary embeddings only")
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise NotImplementedError(
            f"indexer_num_kv_heads={sa['indexer_num_kv_heads']}: one index "
            "key a position only")
    if sa["topk"] < 1:
        raise NotImplementedError(f"topk={sa['topk']}: nothing to select")

    def attention(i):
        return SparseSelectAttentionLayer(
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_size=config["head_dim"], qk_norm=True,
            eps=config["rms_norm_eps"],
            rope_theta=float(config["rope_theta"]),
            index_heads=sa["indexer_num_heads"],
            index_head_size=sa["indexer_head_dim"], topk=sa["topk"])

    def mlp(i):
        return SparseExpertLayer(
            num_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"],
            n_hidden=config["moe_intermediate_size"], held=held,
            scoring="softmax")

    return decoder_stack(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        n_layers=config["num_hidden_layers"], eps=config["rms_norm_eps"],
        attention=attention, mlp=mlp, seq_len=seq_len, updater=updater,
        dtype=dtype, workspace_mode=workspace_mode, seed=seed)
