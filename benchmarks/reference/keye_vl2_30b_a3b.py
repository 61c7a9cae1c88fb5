"""Plain Keye-VL-2.0-30B-A3B language model with its next-token loss: one
chip's share.

The benchmark's reference for the ``keye_vl2_30b_a3b`` configuration
(Kwai-Keye Keye-VL-2.0-30B-A3B ``config.json``, ``model_type: KeyeVL2``, the
language model's keys; the indexer is DeepSeek-V3.2-Exp's at ``sa_config``'s
sizes), in straightforward ``jax.numpy`` at float32 / ``highest``. It imports
nothing from the program under test and takes nothing the program made. A
layer, with ``h`` ``[T, hidden]``:

1. ``a = RMSNorm(h)``; ``q = a Wq`` as ``[T, 32, 128]``, ``k = a Wk`` and
   ``v = a Wv`` as ``[T, 4, 128]``; query head ``i`` reads KV head ``i //
   8``. No biases.
2. ``q`` and ``k`` pass an RMSNorm over each head's 128 channels (gains
   ``gq`` / ``gk`` ``[128]``, one for all heads) and then the rotary
   embedding over the whole head: channel ``i`` paired with ``i + 64``
   (``rotate_half``), angle ``t / 1e7^(2i / 128)``. ``mrope_section`` splits
   the 64 pairs over three position streams that are one on token ids.
3. The indexer: ``qI = a WqI`` as ``[T, 16, 64]``, ``kI = a WkI`` ONE key
   ``[T, 64]``, ``w = a Ww`` ``[T, 16]``; ``I[t, s] = sum_j w[t, j]
   relu(qI[t, j] . kI[s])`` for ``s <= t``.
4. The selection: ``S_t`` = the keys of the ``topk`` largest ``I[t, s]``, ``s
   <= t`` (all of them where ``t + 1 <= topk``), by ``jax.lax.top_k`` on the
   row: exactly ``topk``, the lower key index first among equals. The mask is
   made from the top-k's own values and indices: open above the ``topk``-th
   value, and at that value up to the largest index the top-k took. No
   gradient passes through it.
5. ``o[t, i] = sum_{s in S_t} softmax_s(q[t, i] . k[s, i // 8] / sqrt(128))
   v[s, i // 8]``; ``h = h + concat(o) Wo``; ``b = RMSNorm(h)``.
6. ``p = softmax(b Wr)`` over all routed experts; the ``num_experts_per_tok``
   largest; ``w_e = p_e / sum(p over the chosen)``. ``h = h + sum over the
   chosen experts held here of w_e FFN_e(b)``, gated silu feed-forwards of
   width 768; no shared expert.
7. Final RMSNorm, untied head, mean cross-entropy of position ``t`` against
   token ``t + 1`` over the positions that have a next token.

The indexer's ``WqI``, ``WkI`` and ``Ww`` are leaves of ``init_weights`` like
any other. They enter the loss only through the indices of a top-k, so their
gradient is exactly zero and Adam leaves them where they are: the frozen
indexer the configuration states.

Departures from the published model, all stated in
``configs/keye_vl2_30b_a3b.json``: six of 48 layers; of 128 experts the 8
from ``deployment.held`` (what the others would add is left out, and that
partial sum goes on); the vocabulary's first eighth; no vision tower (token
ids only); the indexer frozen (no alignment loss); the per-head norms and the
indexer's form assumed from the families the row names; the initialiser;
labels are the inputs shifted by one (the ``y`` the harness makes is not
read).

Computed so that 16,384 tokens fit beside the optimizer's state: a layer at
a time, inside it a sequence at a time (each recomputed whole in the backward
pass), and after the sequence's keys, values and index keys a chunk of 128
positions at a time through everything else (the chunk's index scores against
every key, its top-k and mask, attention, output projection, experts), every
held expert over every token with the weights of the tokens that did not
choose it at nought, each chunk recomputed in the backward pass.

Leaves are named ``<vertex>/<param>`` as the program's graph names them;
matrices are ``[in, out]``. ``precision`` is ``common.round_operand``'s: it
rounds the operands of every matrix product, the index products among them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import HI as _HI, make_weights, round_operand as _round

_Q_BLOCK = 128


def layer_table(cfg):
    """[(leaf name, shape, init)] in a fixed order."""
    d, heads, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
    hd, sa = cfg["head_dim"], cfg["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    std = ("normal", cfg["assumed"]["initializer_std"])
    held = cfg["deployment"]["held"][1]
    routed = cfg["deployment"]["num_experts_routed"]
    f = cfg["moe_intermediate_size"]
    out = [("embed/W", (cfg["vocab_size"], d), std)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        out += [(p + "attn_norm/g", (d,), "ones"),
                (p + "attn/Wq", (d, heads * hd), std),
                (p + "attn/Wk", (d, kv * hd), std),
                (p + "attn/Wv", (d, kv * hd), std),
                (p + "attn/Wo", (heads * hd, d), std),
                (p + "attn/gq", (hd,), "ones"),
                (p + "attn/gk", (hd,), "ones"),
                (p + "attn/WqI", (d, ih * idim), std),
                (p + "attn/WkI", (d, idim), std),
                (p + "attn/Ww", (d, ih), std),
                (p + "mlp_norm/g", (d,), "ones"),
                (p + "mlp/Wr", (d, routed), std),
                (p + "mlp/W1", (held, d, f), std),
                (p + "mlp/W3", (held, d, f), std),
                (p + "mlp/W2", (held, f, d), std)]
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
    """``x`` ``[T, heads, d]``, ``cos`` / ``sin`` ``[T, d / 2]``: channel ``i``
    paired with ``i + d / 2`` and turned by the position's angle ``i``."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def index_scores(q_idx, k_idx, w, precision):
    """``q_idx`` ``[C, Hi, di]``, ``k_idx`` ``[T, di]``, ``w`` ``[C, Hi]`` ->
    ``I`` ``[C, T]``."""
    dots = jnp.einsum("chd,sd->hcs", _round(q_idx, precision),
                      _round(k_idx, precision), precision=_HI)
    return jnp.sum(jax.nn.relu(dots) * w.T[:, :, None], axis=0)


def open_keys(scores, q0, topk):
    """``scores`` ``[C, T]`` of the queries at ``q0 .. q0 + C - 1`` -> ``[C,
    T]`` bool: the ``topk`` largest of each row among the keys ``s <= t``
    (``jax.lax.top_k``: the lower index first among equals), all of them
    where there are no more than ``topk``."""
    C, T = scores.shape
    t = q0 + jnp.arange(C)[:, None]
    s = jnp.arange(T)[None, :]
    causal = s <= t
    if topk >= T:
        return causal
    masked = jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf)
    top_v, top_i = jax.lax.top_k(masked, topk)
    kth = top_v[:, -1:]
    # of the keys that score exactly the topk-th value, top_k took the lowest
    # indices: up to the largest it returned
    last = jnp.max(jnp.where(top_v == kth, top_i, -1), axis=-1, keepdims=True)
    return ((masked > kth) | ((masked == kth) & (s <= last))) & causal


def _attend(q, k, v, open_, precision):
    """``q`` ``[C, H, d]`` against ``k`` / ``v`` ``[T, KV, d]`` under
    ``open_`` ``[C, T]``. -> ``[C, H * d]``."""
    C, H, d = q.shape
    KV = k.shape[1]
    r = lambda a: _round(a, precision)
    qg = q.reshape(C, KV, H // KV, d)
    s = jnp.einsum("qkgd,skd->kgqs", r(qg), r(k), precision=_HI) \
        / math.sqrt(d)
    # softmax written out, the row's maximum and sum behind a barrier: left
    # to itself the TPU compiler makes a row-wide reduce-window of them
    s = jnp.where(open_[None, None], s, -jnp.inf)
    m = jax.lax.optimization_barrier(
        jnp.max(jax.lax.stop_gradient(s), axis=-1, keepdims=True))
    e = jnp.exp(s - m)
    p = e / jax.lax.optimization_barrier(jnp.sum(e, axis=-1, keepdims=True))
    o = jnp.einsum("kgqs,skd->qkgd", r(p), r(v), precision=_HI)
    return o.reshape(C, H * d)


def _route(p, pre, b, cfg, precision):
    """-> (chosen expert ids ``[T, k]``, their weights ``[T, k]``): softmax
    over all routed experts, the largest, renormalised over the chosen."""
    probs = jax.nn.softmax(_mm(b, p[pre + "Wr"], precision), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def _experts(p, pre, b, cfg, precision):
    """Every held expert over every token, the weight of a token that did
    not choose the expert at nought. The held experts side by side are one
    gated feed-forward of width ``held x width`` whose hidden units carry
    their expert's weight for the token."""
    first, held = cfg["deployment"]["held"]
    top_e, w = _route(p, pre, b, cfg, precision)
    here = first + jnp.arange(held)
    w_e = jnp.sum(jnp.where(top_e[:, :, None] == here, w[:, :, None], 0.0),
                  axis=1)                                        # [T, held]
    d, f = p[pre + "W1"].shape[1:]
    w1 = p[pre + "W1"].transpose(1, 0, 2).reshape(d, held * f)
    w3 = p[pre + "W3"].transpose(1, 0, 2).reshape(d, held * f)
    hidden = jax.nn.silu(_mm(b, w1, precision)) * _mm(b, w3, precision)
    return _mm(hidden * jnp.repeat(w_e, f, axis=1),
               p[pre + "W2"].reshape(held * f, d), precision)


def _chunks(T):
    return _Q_BLOCK if T % _Q_BLOCK == 0 else T


def _layer(p, i, h, cfg, precision):
    """One sequence ``[T, hidden]`` through decoder layer ``i``. Keys, values
    and index keys for the whole sequence first; then a chunk of ``_Q_BLOCK``
    positions at a time through everything else, each chunk recomputed in
    the backward pass."""
    pre = f"l{i}."
    T, eps = h.shape[0], cfg["rms_norm_eps"]
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    sa = cfg["sa_config"]
    inv = 1.0 / (float(cfg["rope_theta"])
                 ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    a = _rms(h, p[pre + "attn_norm/g"], eps)
    k = _mm(a, p[pre + "attn/Wk"], precision).reshape(T, kv, hd)
    k = _rotate(_rms(k, p[pre + "attn/gk"], eps), cos, sin)
    v = _mm(a, p[pre + "attn/Wv"], precision).reshape(T, kv, hd)
    k_idx = _mm(a, p[pre + "attn/WkI"], precision)
    C = _chunks(T)

    @jax.checkpoint
    def chunk(args):
        n, hc, cs, sn = args
        a = _rms(hc, p[pre + "attn_norm/g"], eps)
        q = _mm(a, p[pre + "attn/Wq"], precision).reshape(C, heads, hd)
        q = _rotate(_rms(q, p[pre + "attn/gq"], eps), cs, sn)
        q_idx = _mm(a, p[pre + "attn/WqI"], precision).reshape(
            C, sa["indexer_num_heads"], sa["indexer_head_dim"])
        scores = index_scores(q_idx, k_idx, _mm(a, p[pre + "attn/Ww"],
                                                precision), precision)
        o = _attend(q, k, v, open_keys(scores, n * C, sa["topk"]), precision)
        hc = hc + _mm(o, p[pre + "attn/Wo"], precision)
        b = _rms(hc, p[pre + "mlp_norm/g"], eps)
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
