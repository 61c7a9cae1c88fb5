"""Plain BERT-base encoder with a mean-pool classification head.

The benchmark's reference for the ``bert_base`` configuration
(``google-bert/bert-base-uncased`` ``config.json``; Devlin et al.,
arXiv:1810.04805): embeddings (word + position + token type 0, LayerNorm),
``num_hidden_layers`` post-LayerNorm encoder layers (multi-head
self-attention with an all-ones attention mask, erf GeLU feed-forward), the
mean over positions, a linear head and softmax cross-entropy, in
straightforward ``jax.numpy`` at float32 / ``highest``. It imports nothing
from the program under test and takes nothing the program made.

Departures, all stated in ``configs/bert_base.json``: dropout is the
identity (the program imports a frozen inference-mode graph), the pooler is
left out (``last_hidden_state`` does not pass through it), the head is the
assumed mean-pool + linear layer, and weights are plain normal(0,
``initializer_range``) where the published initialiser truncates at two
standard deviations.

Leaves are named ``embeddings/...``, ``layer<i>/...`` and ``cls/...``;
matrices are ``[in, out]``. ``precision`` is ``common.round_operand``'s.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .common import HI as _HI, make_weights, round_operand as _round_operand


def layer_table(cfg):
    """[(leaf name, shape, init)] in a fixed order."""
    d, inter = cfg["hidden_size"], cfg["intermediate_size"]
    std = ("normal", cfg["initializer_range"])
    out = [("embeddings/word", (cfg["vocab_size"], d), std),
           ("embeddings/position", (cfg["max_position_embeddings"], d), std),
           ("embeddings/token_type", (cfg["type_vocab_size"], d), std),
           ("embeddings/ln_gamma", (d,), "ones"),
           ("embeddings/ln_beta", (d,), "zeros")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}"
        for name in ("q", "k", "v", "attn_out"):
            out.append((f"{p}/{name}_W", (d, d), std))
            out.append((f"{p}/{name}_b", (d,), "zeros"))
        out.append((f"{p}/attn_ln_gamma", (d,), "ones"))
        out.append((f"{p}/attn_ln_beta", (d,), "zeros"))
        out.append((f"{p}/ffn_in_W", (d, inter), std))
        out.append((f"{p}/ffn_in_b", (inter,), "zeros"))
        out.append((f"{p}/ffn_out_W", (inter, d), std))
        out.append((f"{p}/ffn_out_b", (d,), "zeros"))
        out.append((f"{p}/ffn_ln_gamma", (d,), "ones"))
        out.append((f"{p}/ffn_ln_beta", (d,), "zeros"))
    out.append(("cls/W", (d, cfg["assumed"]["head_classes"]), std))
    out.append(("cls/b", (cfg["assumed"]["head_classes"],), "zeros"))
    return out


def init_weights(seed: int, cfg) -> dict:
    """All float32 master weights, made on the device in one jitted call."""
    return make_weights(layer_table(cfg), seed)


def _dense(x, w, b, precision):
    return jnp.einsum("...i,io->...o", _round_operand(x, precision),
                      _round_operand(w, precision), precision=_HI) + b


def _layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


def _encoder_layer(p, pre, x, cfg, precision):
    b, t, d = x.shape
    h = cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]

    def heads(name):
        y = _dense(x, p[f"{pre}/{name}_W"], p[f"{pre}/{name}_b"], precision)
        return y.reshape(b, t, h, d // h).transpose(0, 2, 1, 3)

    q, k, v = heads("q"), heads("k"), heads("v")
    scores = jnp.einsum("bhqd,bhkd->bhqk", _round_operand(q, precision),
                        _round_operand(k, precision),
                        precision=_HI) / (d // h) ** 0.5
    probs = jax.nn.softmax(scores, axis=-1)     # all-ones mask adds nothing
    ctx = jnp.einsum("bhqk,bhkd->bhqd", _round_operand(probs, precision),
                     _round_operand(v, precision), precision=_HI)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, d)
    a = _dense(ctx, p[f"{pre}/attn_out_W"], p[f"{pre}/attn_out_b"], precision)
    x = _layer_norm(x + a, p[f"{pre}/attn_ln_gamma"],
                    p[f"{pre}/attn_ln_beta"], eps)
    f = jax.nn.gelu(_dense(x, p[f"{pre}/ffn_in_W"], p[f"{pre}/ffn_in_b"],
                           precision), approximate=False)
    f = _dense(f, p[f"{pre}/ffn_out_W"], p[f"{pre}/ffn_out_b"], precision)
    return _layer_norm(x + f, p[f"{pre}/ffn_ln_gamma"],
                       p[f"{pre}/ffn_ln_beta"], eps)


def logits(p, ids, cfg, precision="float32"):
    """[B, T] token ids -> [B, classes] float32 logits. Each encoder layer
    is rematerialised in the backward pass so that float32 at the timed
    batch fits one chip."""
    t = ids.shape[1]
    x = (p["embeddings/word"][ids] + p["embeddings/position"][:t]
         + p["embeddings/token_type"][0])
    x = _layer_norm(x, p["embeddings/ln_gamma"], p["embeddings/ln_beta"],
                    cfg["layer_norm_eps"])
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda p, x, i=i: _encoder_layer(p, f"layer{i}", x, cfg,
                                             precision))(p, x)
    pooled = jnp.mean(x, axis=1)
    return _dense(pooled, p["cls/W"], p["cls/b"], precision)


def loss(p, batch, cfg, precision="float32"):
    """Mean softmax cross-entropy against one-hot labels."""
    ids, y = batch
    lg = logits(p, ids, cfg, precision)
    return -jnp.mean(jnp.sum(y * jax.nn.log_softmax(lg, axis=-1), axis=-1))
