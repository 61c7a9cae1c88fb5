"""Plain kanana-2-30b-a3b decoder with its next-token loss: one chip's share.

The benchmark's reference for the ``kanana2_30b_a3b`` configuration
(kakaocorp kanana-2-30b-a3b-instruct-2601 ``config.json``, ``model_type:
deepseek_v3``; the block of arXiv:2412.19437 section 2.1 without the query's
low-rank projection and with one selection group), in straightforward
``jax.numpy`` at float32 / ``highest``. It imports nothing from the program
under test and takes nothing the program made. A layer, with ``h`` ``[T,
hidden]``:

1. ``a = RMSNorm(h)``; ``q = a Wq`` as ``[T, 32, 192]``: ``q_nope`` the
   first 128 of a head, ``q_pe`` the last 64.
2. ``ckv = a Wkva`` ``[T, 576]``; ``c = RMSNorm(ckv[:, :512]; g_kv)``;
   ``k_pe = ckv[:, 512:]``, ONE rotary key ``[T, 64]`` for all heads.
3. ``kv = c Wkvb`` as ``[T, 32, 256]``: ``k_nope`` the first 128, ``v`` the
   last 128.
4. Rotary embedding on ``q_pe`` and ``k_pe``: the 32 pairs ``(2i, 2i + 1)``
   rotated IN PLACE by ``t / 1e6^(2i / 64)`` (DeepSeek's own
   ``apply_rotary_emb``, the pairs read as complex numbers; HF's port
   de-interleaves to ``(i, i + 32)`` and applies ``rotate_half``, a fixed
   permutation of the channels of ``q_pe`` and ``k_pe`` alike that no score
   sees). No scaling.
5. ``o = softmax((q_nope k_nope^T + q_pe k_pe^T) / sqrt(192) + causal) v``
   as ``[T, 32 x 128]``; ``h = h + o Wo``; ``b = RMSNorm(h)``.
6. Layer 0 (``first_k_dense_replace`` 1): ``h = h + (silu(b W1) * (b W3))
   W2`` at width 6144.
7. The others: ``s = sigmoid(b Wr)`` over all routed experts; the
   ``num_experts_per_tok`` largest of ``s + bias``; ``w = 2.448 s_chosen /
   (sum(s_chosen) + 1e-20)``: chosen on the biased scores, weighed by the
   unbiased ones. ``h = h + sum over the chosen experts held here of w_e
   FFN_e(b) + FFN_shared(b)``, the two shared experts one gated feed-forward
   of width 2 x 768 as HF's ``DeepseekV3MoE`` builds it.
8. Final RMSNorm, untied head, mean cross-entropy of position ``t`` against
   token ``t + 1`` over the positions that have a next token.

The selection bias (``e_score_correction_bias``) is a leaf of
``init_weights`` like any other, drawn from the seed. It enters the loss
only through the indices of a top-k, so its gradient is exactly zero and
Adam leaves it where it is: the frozen-bias regime the configuration states.

Departures from the published model, all stated in
``configs/kanana2_30b_a3b.json``: six of 48 layers; of 128 routed experts
the 8 from ``deployment.held`` (what the others would add is left out, and
that partial sum goes on); the vocabulary's first eighth; the bias frozen;
the initialiser; labels are the inputs shifted by one (the ``y`` the harness
makes is not read).

Computed so that 16,384 tokens fit beside the optimizer's state: a layer at
a time, inside it a sequence at a time (each recomputed whole in the
backward pass, so that the expanded keys and values of one sequence, 268 MB,
are held once and not once a layer), and after the sequence's keys and
values a chunk of 128 positions at a time through everything else, every
held expert over every token with the weights of the tokens that did not
choose it at nought, each chunk recomputed in the backward pass.

Leaves are named ``<vertex>/<param>`` as the program's graph names them;
matrices are ``[in, out]``. ``precision`` is ``common.round_operand``'s.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import HI as _HI, make_weights, round_operand as _round

_Q_BLOCK = 128


def _is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def layer_table(cfg):
    """[(leaf name, shape, init)] in a fixed order."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    std = ("normal", cfg["assumed"]["initializer_std"])
    bias = ("normal", cfg["assumed"]["select_bias_std"])
    held = cfg["deployment"]["held"][1]
    routed = cfg["deployment"]["num_experts_routed"]
    f = cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    out = [("embed/W", (cfg["vocab_size"], d), std)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        out += [(p + "attn_norm/g", (d,), "ones"),
                (p + "attn/Wq", (d, heads * (nope + rope)), std),
                (p + "attn/Wkva", (d, rank + rope), std),
                (p + "attn/g_kv", (rank,), "ones"),
                (p + "attn/Wkvb", (rank, heads * (nope + vd)), std),
                (p + "attn/Wo", (heads * vd, d), std),
                (p + "mlp_norm/g", (d,), "ones")]
        if _is_dense(cfg, i):
            w = cfg["intermediate_size"]
            out += [(p + "mlp/W1", (d, w), std), (p + "mlp/W3", (d, w), std),
                    (p + "mlp/W2", (w, d), std)]
        else:
            out += [(p + "mlp/Wr", (d, routed), std),
                    (p + "mlp/select_bias", (routed,), bias),
                    (p + "mlp/W1", (held, d, f), std),
                    (p + "mlp/W3", (held, d, f), std),
                    (p + "mlp/W2", (held, f, d), std),
                    (p + "mlp/S1", (d, fs), std), (p + "mlp/S3", (d, fs), std),
                    (p + "mlp/S2", (fs, d), std)]
    out += [("norm/g", (d,), "ones"), ("lm_head/W", (d, cfg["vocab_size"]), std)]
    return out


def init_weights(seed: int, cfg) -> dict:
    """All float32 master weights, made on the device in one jitted call."""
    return make_weights(layer_table(cfg), seed)


def _mm(x, w, precision):
    return jnp.einsum("...i,io->...o", _round(x, precision),
                      _round(w, precision), precision=_HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate(x, cos, sin):
    """``x`` ``[T, ..., rope]``, ``cos`` / ``sin`` ``[T, rope / 2]``: channel
    pair ``(2i, 2i + 1)`` turned by the position's angle ``i``, in place."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                     axis=-1).reshape(x.shape)


def _attend(q_nope, q_pe, k_nope, k_pe, v, q0, precision):
    """``q_nope`` ``[C, H, nope]`` and ``q_pe`` ``[C, H, rope]`` from position
    ``q0`` against every key: ``k_nope`` ``[T, H, nope]``, the one ``k_pe``
    ``[T, rope]``, ``v`` ``[T, H, vd]``. -> ``[C, H * vd]``."""
    C, H, nope = q_nope.shape
    r = lambda a: _round(a, precision)
    s = (jnp.einsum("qhd,khd->hqk", r(q_nope), r(k_nope), precision=_HI)
         + jnp.einsum("qhd,kd->hqk", r(q_pe), r(k_pe), precision=_HI)) \
        / math.sqrt(nope + q_pe.shape[-1])
    qi = q0 + jnp.arange(C)[:, None]
    kj = jnp.arange(k_pe.shape[0])[None, :]
    # softmax written out, the row's maximum and sum behind a barrier: left
    # to itself the TPU compiler makes a row-wide reduce-window of them
    s = jnp.where(kj <= qi, s, -jnp.inf)
    m = jax.lax.optimization_barrier(
        jnp.max(jax.lax.stop_gradient(s), axis=-1, keepdims=True))
    e = jnp.exp(s - m)
    p = e / jax.lax.optimization_barrier(jnp.sum(e, axis=-1, keepdims=True))
    o = jnp.einsum("hqk,khd->qhd", r(p), r(v), precision=_HI)
    return o.reshape(C, -1)


def _gated(x, w1, w3, w2, precision):
    return _mm(jax.nn.silu(_mm(x, w1, precision)) * _mm(x, w3, precision),
               w2, precision)


def _route(p, pre, b, cfg, precision):
    """-> (chosen expert ids ``[T, k]``, their weights ``[T, k]``): chosen by
    the biased scores, weighed by the unbiased ones."""
    s = jax.nn.sigmoid(_mm(b, p[pre + "Wr"], precision))
    _, top_e = jax.lax.top_k(s + p[pre + "select_bias"],
                             cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    w = cfg["routed_scaling_factor"] * top_s \
        / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return top_e, w


def _experts(p, pre, b, cfg, precision):
    """Every held expert over every token, the weight of a token that did
    not choose the expert at nought; the shared experts once. The held
    experts side by side are one gated feed-forward of width ``held x
    width`` whose hidden units carry their expert's weight for the token."""
    first, held = cfg["deployment"]["held"]
    top_e, w = _route(p, pre, b, cfg, precision)
    here = first + jnp.arange(held)
    w_e = jnp.sum(jnp.where(top_e[:, :, None] == here, w[:, :, None], 0.0),
                  axis=1)                                        # [T, held]
    d, f = p[pre + "W1"].shape[1:]
    w1 = p[pre + "W1"].transpose(1, 0, 2).reshape(d, held * f)
    w3 = p[pre + "W3"].transpose(1, 0, 2).reshape(d, held * f)
    hidden = jax.nn.silu(_mm(b, w1, precision)) * _mm(b, w3, precision)
    routed = _mm(hidden * jnp.repeat(w_e, f, axis=1),
                 p[pre + "W2"].reshape(held * f, d), precision)
    return routed + _gated(b, p[pre + "S1"], p[pre + "S3"], p[pre + "S2"],
                           precision)


def _chunks(T):
    return _Q_BLOCK if T % _Q_BLOCK == 0 else T


def _layer(p, i, h, cfg, precision):
    """One sequence ``[T, hidden]`` through decoder layer ``i``. The latent,
    the rotary key and the expanded keys and values for the whole sequence
    first; then a chunk of ``_Q_BLOCK`` positions at a time through
    everything else (queries, attention over every key under the mask,
    output projection, feed-forward), each chunk recomputed in the backward
    pass."""
    pre = f"l{i}."
    T, eps = h.shape[0], cfg["rms_norm_eps"]
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    inv = 1.0 / (float(cfg["rope_theta"])
                 ** (np.arange(0, rope, 2, dtype=np.float64) / rope))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    a = _rms(h, p[pre + "attn_norm/g"], eps)
    ckv = _mm(a, p[pre + "attn/Wkva"], precision)
    k_pe = _rotate(ckv[:, rank:], cos, sin)
    kv = _mm(_rms(ckv[:, :rank], p[pre + "attn/g_kv"], eps),
             p[pre + "attn/Wkvb"], precision).reshape(T, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    C = _chunks(T)

    @jax.checkpoint
    def chunk(args):
        n, hc, cs, sn = args
        a = _rms(hc, p[pre + "attn_norm/g"], eps)
        q = _mm(a, p[pre + "attn/Wq"], precision).reshape(C, heads, -1)
        o = _attend(q[..., :nope], _rotate(q[..., nope:], cs, sn), k_nope,
                    k_pe, v, n * C, precision)
        hc = hc + _mm(o, p[pre + "attn/Wo"], precision)
        b = _rms(hc, p[pre + "mlp_norm/g"], eps)
        if _is_dense(cfg, i):
            return hc + _gated(b, p[pre + "mlp/W1"], p[pre + "mlp/W3"],
                               p[pre + "mlp/W2"], precision)
        return hc + _experts(p, pre + "mlp/", b, cfg, precision)

    cut = lambda x: x.reshape((T // C, C) + x.shape[1:])
    out = jax.lax.map(chunk, (jnp.arange(T // C), cut(h), cut(cos), cut(sin)))
    return out.reshape(T, -1)


def hidden(p, ids, cfg, precision="float32"):
    """``[B, T]`` token ids -> ``[B, T, hidden]`` after the final norm: a
    layer at a time over the batch, a sequence at a time inside it, each
    (layer, sequence) recomputed whole in the backward pass."""
    h = p["embed/W"][ids]
    for i in range(cfg["num_hidden_layers"]):
        own = {k: v for k, v in p.items() if k.startswith(f"l{i}.")}
        one = jax.checkpoint(
            lambda own, row, i=i: _layer(own, i, row, cfg, precision))
        h = jnp.stack([one(own, row) for row in h])
    return _rms(h, p["norm/g"], cfg["rms_norm_eps"])


def logits(p, ids, cfg, precision="float32"):
    """``[B, T]`` token ids -> ``[B, T, vocabulary held]`` float32 logits."""
    return _mm(hidden(p, ids, cfg, precision), p["lm_head/W"], precision)


def loss(p, batch, cfg, precision="float32"):
    """Mean next-token cross-entropy over the positions that have a next
    token; the labels are ``batch[0]`` shifted by one. The head, like the
    layers, takes a chunk of positions at a time."""
    ids = jnp.asarray(batch[0], jnp.int32)
    B, T = ids.shape
    C = _chunks(T)
    nxt = jnp.roll(ids, -1, axis=1)
    scored = jnp.broadcast_to(jnp.arange(T) < T - 1, (B, T))

    @jax.checkpoint
    def chunk_nll(args):
        h, y, m = args
        lg = _mm(h, p["lm_head/W"], precision)
        picked = jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(lg, axis=-1) - picked) * m)

    cut = lambda x: x.reshape((B * T // C, C) + x.shape[2:])
    total = jnp.sum(jax.lax.map(
        chunk_nll, (cut(hidden(p, ids, cfg, precision)), cut(nxt),
                    cut(scored))))
    return total / (B * (T - 1))
