"""Plain Laguna-XS.2 decoder with its next-token loss: one chip's share.

The benchmark's reference for the ``laguna_xs2`` configuration (poolside
Laguna-XS.2 ``config.json``), in straightforward ``jax.numpy`` at float32 /
``highest``. It imports nothing from the program under test and takes
nothing the program made. A layer, with ``h`` ``[T, hidden]``:

1. ``a = RMSNorm(h)``; ``q = a Wq`` as ``[T, H_l, 128]``, ``k``, ``v`` as
   ``[T, 8, 128]``; query head ``i`` reads KV head ``i // (H_l / 8)``.
2. Rotary embedding on ``q`` and ``k``: full layers the first half of the
   head's dimensions with YaRN frequencies and cos / sin times
   ``attention_factor``, window layers all of them at base 10,000.
3. ``o = softmax(q k^T / sqrt(128) + mask) v``, causal; in window layers key
   ``j`` is open to query ``i`` only where ``i - 512 < j <= i``.
4. ``o = o * sigmoid(a Wg)``; 5. ``h = h + o Wo``; ``b = RMSNorm(h)``.
6. Layer 0: ``h = h + (silu(b W1) * (b W3)) W2``.
7. Sparse layers: ``s = sigmoid(b Wr)`` over all routed experts, the
   ``num_experts_per_tok`` largest, ``w = 2.5 s / sum(s)``; ``h = h + sum
   over the chosen experts held here of w_e FFN_e(b) + FFN_shared(b)``.
8. Final RMSNorm, untied head, mean cross-entropy of position ``t`` against
   token ``t + 1`` over the positions that have a next token.

Departures from the published model, all stated in
``configs/laguna_xs2.json``: five of forty layers; of 256 routed experts the
16 from ``deployment.held`` (what the others would add is left out, and that
partial sum goes on); the vocabulary's first eighth; the output gate, the
activation, the router's scoring and normalisation and the initialiser are
assumed readings; labels are the inputs shifted by one (the ``y`` the harness
makes is not read).

Computed so that 16,384 tokens fit beside the optimizer's state: a layer at
a time, inside it a sequence at a time, and after the sequence's keys and
values a chunk of 128 positions at a time (a window layer's chunk over the
keys its window reaches, a full layer's over all keys under the mask), every
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


def layer_table(cfg):
    """[(leaf name, shape, init)] in a fixed order."""
    d, hd, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    std = ("normal", cfg["assumed"]["initializer_std"])
    held = cfg["deployment"]["held"][1]
    routed = cfg["deployment"]["num_experts_routed"]
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    out = [("embed/W", (cfg["vocab_size"], d), std)]
    for i in range(cfg["num_hidden_layers"]):
        p, hq = f"l{i}.", cfg["num_attention_heads_per_layer"][i] * hd
        out += [(p + "attn_norm/g", (d,), "ones"),
                (p + "attn/Wq", (d, hq), std), (p + "attn/Wk", (d, kv * hd), std),
                (p + "attn/Wv", (d, kv * hd), std), (p + "attn/Wg", (d, hq), std),
                (p + "attn/Wo", (hq, d), std),
                (p + "mlp_norm/g", (d,), "ones")]
        if cfg["mlp_layer_types"][i] == "dense":
            w = cfg["intermediate_size"]
            out += [(p + "mlp/W1", (d, w), std), (p + "mlp/W3", (d, w), std),
                    (p + "mlp/W2", (w, d), std)]
        else:
            out += [(p + "mlp/Wr", (d, routed), std),
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


def _inv_freq(rope, head_dim):
    """The rotary frequencies of one kind of layer, as HF's
    ``ROPE_INIT_FUNCTIONS`` give them for ``default`` and ``yarn``."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1))
    base = rope["rope_theta"]
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return 1.0 / pos_freqs, 1.0
    factor = rope["factor"]
    orig = rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extrapolation_share = 1 - ramp
    inv = (1.0 / (factor * pos_freqs)) * (1 - extrapolation_share) \
        + (1.0 / pos_freqs) * extrapolation_share
    return inv, rope["attention_factor"]


def _rotate(x, cos, sin):
    """``x`` ``[T, heads, d]``: ``x * cos + rotate_half(x) * sin`` on the
    first ``2 * cos.shape[-1]`` dimensions."""
    n = cos.shape[-1]
    x1, x2, rest = x[..., :n], x[..., n:2 * n], x[..., 2 * n:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def _attend(q, k, v, q0, k0, window, precision):
    """``q`` ``[C, H, d]`` from position ``q0`` against ``k`` / ``v`` ``[nk,
    KV, d]`` from position ``k0`` (keys before position 0 are padding).
    -> ``[C, H * d]``."""
    C, H, d = q.shape
    kv = k.shape[1]
    q = q.reshape(C, kv, H // kv, d)
    s = jnp.einsum("qhgd,khd->hgqk", _round(q, precision),
                   _round(k, precision), precision=_HI) / math.sqrt(d)
    qi = q0 + jnp.arange(C)[:, None]
    kj = k0 + jnp.arange(k.shape[0])[None, :]
    open_ = (kj <= qi) & (kj >= 0)
    if window is not None:
        open_ &= kj > qi - window
    # softmax written out, the row's maximum and sum behind a barrier: left
    # to itself the TPU compiler makes a row-wide reduce-window of them
    s = jnp.where(open_, s, -jnp.inf)
    m = jax.lax.optimization_barrier(
        jnp.max(jax.lax.stop_gradient(s), axis=-1, keepdims=True))
    e = jnp.exp(s - m)
    p = e / jax.lax.optimization_barrier(jnp.sum(e, axis=-1, keepdims=True))
    o = jnp.einsum("hgqk,khd->qhgd", _round(p, precision),
                   _round(v, precision), precision=_HI)
    return o.reshape(C, H * d)


def _gated(x, w1, w3, w2, precision):
    return _mm(jax.nn.silu(_mm(x, w1, precision)) * _mm(x, w3, precision),
               w2, precision)


def _experts(p, pre, b, cfg, precision):
    """Every held expert over every token, the weight of a token that did
    not choose the expert at nought; the shared expert once. The held
    experts side by side are one gated feed-forward of width ``held x
    width`` whose hidden units carry their expert's weight for the token."""
    first, held = cfg["deployment"]["held"]
    s = jax.nn.sigmoid(_mm(b, p[pre + "Wr"], precision))
    top_s, top_e = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    w = cfg["moe_routed_scaling_factor"] * top_s \
        / jnp.sum(top_s, axis=-1, keepdims=True)
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
    """One sequence ``[T, hidden]`` through decoder layer ``i``. Keys and
    values for the whole sequence first; then a chunk of ``_Q_BLOCK``
    positions at a time through everything else (queries, attention over
    the keys the mask leaves open, gate, output projection, feed-forward),
    each chunk recomputed in the backward pass. A full layer's chunk sees
    every key under its mask, a window layer's the ``chunk + window - 1``
    keys that end with its own."""
    pre = f"l{i}."
    kind = cfg["layer_types"][i]
    T, hd, eps = h.shape[0], cfg["head_dim"], cfg["rms_norm_eps"]
    inv, scale = _inv_freq(cfg["rope_parameters"][kind], hd)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * scale, jnp.float32)
    sin = jnp.asarray(np.sin(ang) * scale, jnp.float32)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    a = _rms(h, p[pre + "attn_norm/g"], eps)
    k = _rotate(_mm(a, p[pre + "attn/Wk"], precision).reshape(T, -1, hd),
                cos, sin)
    v = _mm(a, p[pre + "attn/Wv"], precision).reshape(T, -1, hd)
    C = _chunks(T)
    span = T if window is None else min(T, C + window - 1)
    if span < T:
        pad = jnp.zeros((span - C,) + k.shape[1:], k.dtype)
        k, v = jnp.concatenate([pad, k]), jnp.concatenate([pad, v])

    @jax.checkpoint
    def chunk(args):
        n, hc, cs, sn = args
        a = _rms(hc, p[pre + "attn_norm/g"], eps)
        q = _rotate(_mm(a, p[pre + "attn/Wq"], precision).reshape(C, -1, hd),
                    cs, sn)
        if span == T:
            o = _attend(q, k, v, n * C, 0, window, precision)
        else:
            o = _attend(q, jax.lax.dynamic_slice_in_dim(k, n * C, span),
                        jax.lax.dynamic_slice_in_dim(v, n * C, span),
                        n * C, n * C - (span - C), window, precision)
        o = o * jax.nn.sigmoid(_mm(a, p[pre + "attn/Wg"], precision))
        hc = hc + _mm(o, p[pre + "attn/Wo"], precision)
        b = _rms(hc, p[pre + "mlp_norm/g"], eps)
        if cfg["mlp_layer_types"][i] == "dense":
            return hc + _gated(b, p[pre + "mlp/W1"], p[pre + "mlp/W3"],
                               p[pre + "mlp/W2"], precision)
        return hc + _experts(p, pre + "mlp/", b, cfg, precision)

    cut = lambda x: x.reshape((T // C, C) + x.shape[1:])
    out = jax.lax.map(chunk, (jnp.arange(T // C), cut(h), cut(cos), cut(sin)))
    return out.reshape(T, -1)


def hidden(p, ids, cfg, precision="float32"):
    """``[B, T]`` token ids -> ``[B, T, hidden]`` after the final norm: a
    layer at a time over the batch, a sequence at a time inside it."""
    h = p["embed/W"][ids]
    for i in range(cfg["num_hidden_layers"]):
        own = {k: v for k, v in p.items() if k.startswith(f"l{i}.")}
        h = jnp.stack([_layer(own, i, row, cfg, precision) for row in h])
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
