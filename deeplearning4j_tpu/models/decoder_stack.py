"""The layout every causal decoder builder here shares: token embedding,
``n_layers`` pre-norm decoder layers, a final norm and a next-token loss
head, as a :class:`ComputationGraph` on token ids. The builders
(``models/laguna.py``, ``models/kanana.py``, ``models/ouro.py``) say what
layer ``i``'s attention and feed-forward are.

A layer is ``l<i>.attn_norm``, ``.attn``, ``.attn_res``, ``.mlp_norm``,
``.mlp``, ``.mlp_res``; with ``post_norms`` a norm follows each sub-layer
too (``.attn_post`` before ``.attn_res``, ``.mlp_post`` before
``.mlp_res``: the sandwich placement). :func:`vertices_per_layer` says how
many vertices that is, so ``workspace_mode=f"every_{vertices_per_layer(
post_norms)}"`` recomputes one decoder layer at a time in the backward
pass.

With ``passes`` above one the layers and the final norm are one repeated
run of the graph (``GraphBuilder.repeat``): walked ``passes`` times with one
set of weights, each pass's normed output entering the next. A ``head``
that ``reads_passes`` is handed every pass's output stacked; any other head
reads the last pass.
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

PASSES = "passes"


def vertices_per_layer(post_norms: bool = False) -> int:
    """Vertices of one decoder layer in the layout asked for."""
    return 8 if post_norms else 6


# the layout without post-norms, under the name its first builders import
VERTICES_PER_LAYER = vertices_per_layer()


def decoder_stack(*, vocab_size: int, hidden_size: int, n_layers: int,
                  eps: float, attention: Callable[[int], Layer],
                  mlp: Callable[[int], Layer], seq_len: int, updater=None,
                  dtype: str = "FLOAT", workspace_mode: Optional[str] = None,
                  seed: int = 0, passes: int = 1, post_norms: bool = False,
                  head: Optional[Layer] = None) -> ComputationGraph:
    """The graph, not yet initialised. ``attention(i)`` / ``mlp(i)`` give
    decoder layer ``i``'s two sub-layers; ``head`` the loss head (None: the
    plain next-token head over ``vocab_size``)."""
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
             .add_layer(p + "attn", attention(i), p + "attn_norm"))
        sub = p + "attn"
        if post_norms:
            g = g.add_layer(p + "attn_post", RMSNormLayer(eps=eps), sub)
            sub = p + "attn_post"
        g = (g.add_vertex(p + "attn_res", ElementWiseVertex(op="add"), h, sub)
             .add_layer(p + "mlp_norm", RMSNormLayer(eps=eps), p + "attn_res")
             .add_layer(p + "mlp", mlp(i), p + "mlp_norm"))
        sub = p + "mlp"
        if post_norms:
            g = g.add_layer(p + "mlp_post", RMSNormLayer(eps=eps), sub)
            sub = p + "mlp_post"
        g = g.add_vertex(p + "mlp_res", ElementWiseVertex(op="add"),
                         p + "attn_res", sub)
        h = p + "mlp_res"
    g = g.add_layer("norm", RMSNormLayer(eps=eps), h)
    head = head or CausalLMOutputLayer(n_out=vocab_size)
    stacked = getattr(head, "reads_passes", False)
    if passes > 1 or stacked:
        g = g.repeat(PASSES, "l0.attn_norm", "norm", passes)
    scored = PASSES if stacked else "norm"
    g = g.add_layer("lm_head", head, scored, "tokens").set_outputs("lm_head")
    return ComputationGraph(g.build())
