"""The layout every causal decoder builder here shares: token embedding,
``n_layers`` pre-norm decoder layers of six vertices each (``l<i>.attn_norm``,
``.attn``, ``.attn_res``, ``.mlp_norm``, ``.mlp``, ``.mlp_res``), a final
norm and the next-token loss head, as a :class:`ComputationGraph` on token
ids. ``workspace_mode="every_6"`` therefore recomputes one decoder layer at
a time in the backward pass. The builders (``models/laguna.py``,
``models/kanana.py``) say what layer ``i``'s attention and feed-forward are.
"""

from __future__ import annotations

from typing import Callable, Optional

from ..nn.config import NeuralNetConfiguration
from ..nn.graph import ComputationGraph
from ..nn.layers.base import Layer
from ..nn.layers.core import EmbeddingLayer
from ..nn.layers.decoder import CausalLMOutputLayer, RMSNormLayer
from ..nn.updaters import Adam
from ..nn.vertices import ElementWiseVertex

VERTICES_PER_LAYER = 6


def decoder_stack(*, vocab_size: int, hidden_size: int, n_layers: int,
                  eps: float, attention: Callable[[int], Layer],
                  mlp: Callable[[int], Layer], seq_len: int, updater=None,
                  dtype: str = "FLOAT", workspace_mode: Optional[str] = None,
                  seed: int = 0) -> ComputationGraph:
    """The graph, not yet initialised. ``attention(i)`` / ``mlp(i)`` give
    decoder layer ``i``'s two sub-layers."""
    b = (NeuralNetConfiguration.builder().seed(seed).data_type(dtype)
         .updater(updater or Adam(learning_rate=1e-4, beta2=0.95)))
    if workspace_mode:
        b = b.workspace_mode(workspace_mode)
    g = (b.graph_builder().add_inputs("tokens").set_input_types((seq_len,))
         .add_layer("embed", EmbeddingLayer(n_in=vocab_size,
                                            n_out=hidden_size),
                    "tokens"))
    h = "embed"
    for i in range(n_layers):
        p = f"l{i}."
        g = (g.add_layer(p + "attn_norm", RMSNormLayer(eps=eps), h)
             .add_layer(p + "attn", attention(i), p + "attn_norm")
             .add_vertex(p + "attn_res", ElementWiseVertex(op="add"),
                         h, p + "attn")
             .add_layer(p + "mlp_norm", RMSNormLayer(eps=eps), p + "attn_res")
             .add_layer(p + "mlp", mlp(i), p + "mlp_norm")
             .add_vertex(p + "mlp_res", ElementWiseVertex(op="add"),
                         p + "attn_res", p + "mlp"))
        h = p + "mlp_res"
    g = (g.add_layer("norm", RMSNormLayer(eps=eps), h)
         .add_layer("lm_head", CausalLMOutputLayer(n_out=vocab_size),
                    "norm", "tokens")
         .set_outputs("lm_head"))
    return ComputationGraph(g.build())
