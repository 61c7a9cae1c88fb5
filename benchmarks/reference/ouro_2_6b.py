"""Plain Ouro-2.6B looped decoder with its exit-weighted loss.

The benchmark's reference for the ``ouro_2_6b`` configuration (ByteDance
Ouro-2.6B ``config.json``, ``model_type: ouro``; arXiv:2510.25741), in
straightforward ``jax.numpy`` at float32 / ``highest``. It imports nothing
from the program under test and takes nothing the program made. With ``L``
layers, ``R = total_ut_steps`` passes, ``N`` RMSNorm (every norm its own
gain) and ``h(0) = E[ids]``:

    for t = 1..R:                       # the same layers, the same weights
        x = h(t-1)
        for l = 1..L:
            a = x + N2_l(Attn_l(N1_l(x)))     # a norm before and after
            x = a + N4_l(FFN_l(N3_l(a)))
        h(t) = Nf(x)                    # the final norm closes every pass
        z(t) = h(t) W_head              # logits of pass t
        g(t) = sigmoid(h(t) w_gate + b_gate)  # exit gate, a scalar a position

``Attn``: ``q, k, v = x Wq, x Wk, x Wv`` as heads of ``head_dim``, no bias;
rotary embedding over the whole head at base ``rope_theta``, channel ``i``
paired with ``i + head_dim / 2`` (``rotate_half``); ``softmax(q k^T /
sqrt(head_dim) + causal) v``; ``Wo``. ``FFN``: ``(silu(x W1) * (x W3)) W2``.

The loss, with ``ce(t, i) = logsumexp(z(t)_i) - z(t)_i[ids_{i+1}]`` at every
position ``i`` that has a next token:

    p(1) = g(1);  p(t) = g(t) prod_{j<t} (1 - g(j));  p(R) = prod_{j<R} (1 - g(j))
    loss = mean_i [ sum_t p(t, i) ce(t, i) - beta H(p(., i)) ],  H(p) = - sum_t p log p

(``g(R)`` is computed by nobody and enters nothing.) The passes are written
out one after the other, so a weight's gradient is the sum of what each pass
gives it by the chain rule alone.

Departures from the published model, all stated in
``configs/ouro_2_6b.json``: four of 48 layers; the placement of the norms,
the gate's form, the objective and ``beta`` are from the paper as recalled
and are listed under ``assumed``; the initialiser; labels are the inputs
shifted by one (the ``y`` the harness makes is not read).

Computed so that 16,384 tokens fit beside the optimizer's state: a layer
application at a time, inside it a sequence at a time (each recomputed whole
in the backward pass), and after the sequence's keys and values a chunk of
128 positions at a time through everything else; the head a chunk of 128
positions of one pass at a time; every chunk recomputed in the backward
pass. ``_Q_BLOCK`` sizes the chunks and changes no result but by rounding.

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
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq = cfg["num_attention_heads"] * hd
    hkv = cfg["num_key_value_heads"] * hd
    w = cfg["intermediate_size"]
    std = ("normal", cfg["assumed"]["initializer_std"])
    out = [("embed/W", (cfg["vocab_size"], d), std)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        out += [(p + "attn_norm/g", (d,), "ones"),
                (p + "attn/Wq", (d, hq), std), (p + "attn/Wk", (d, hkv), std),
                (p + "attn/Wv", (d, hkv), std), (p + "attn/Wo", (hq, d), std),
                (p + "attn_post/g", (d,), "ones"),
                (p + "mlp_norm/g", (d,), "ones"),
                (p + "mlp/W1", (d, w), std), (p + "mlp/W3", (d, w), std),
                (p + "mlp/W2", (w, d), std),
                (p + "mlp_post/g", (d,), "ones")]
    out += [("norm/g", (d,), "ones"),
            ("lm_head/W", (d, cfg["vocab_size"]), std),
            ("lm_head/Wg", (d, 1),
             ("normal", cfg["assumed"]["exit_gate_std"])),
            ("lm_head/bg", (1,), "zeros")]
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
    """``x`` ``[T, heads, d]``, ``cos`` / ``sin`` ``[T, d / 2]``: ``x * cos
    + rotate_half(x) * sin``, channel ``i`` paired with ``i + d / 2``."""
    n = cos.shape[-1]
    x1, x2 = x[..., :n], x[..., n:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attend(q, k, v, q0, precision):
    """``q`` ``[C, H, d]`` from position ``q0`` against every key ``k`` / ``v``
    ``[T, KV, d]``. -> ``[C, H * d]``."""
    C, H, d = q.shape
    kv = k.shape[1]
    q = q.reshape(C, kv, H // kv, d)
    s = jnp.einsum("qhgd,khd->hgqk", _round(q, precision),
                   _round(k, precision), precision=_HI) / math.sqrt(d)
    qi = q0 + jnp.arange(C)[:, None]
    kj = jnp.arange(k.shape[0])[None, :]
    # softmax written out, the row's maximum and sum behind a barrier: left
    # to itself the TPU compiler makes a row-wide reduce-window of them
    s = jnp.where(kj <= qi, s, -jnp.inf)
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


def _chunks(T):
    return _Q_BLOCK if T % _Q_BLOCK == 0 else T


def layer(p, i, h, cfg, precision="float32"):
    """One sequence ``[T, hidden]`` through one application of decoder layer
    ``i``: keys and values for the whole sequence first, then a chunk of
    positions at a time through everything else, each chunk recomputed in
    the backward pass."""
    pre = f"l{i}."
    T, eps, hd = h.shape[0], cfg["rms_norm_eps"], cfg["head_dim"]
    inv = 1.0 / (float(cfg["rope_theta"])
                 ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    a = _rms(h, p[pre + "attn_norm/g"], eps)
    k = _rotate(_mm(a, p[pre + "attn/Wk"], precision).reshape(T, -1, hd),
                cos, sin)
    v = _mm(a, p[pre + "attn/Wv"], precision).reshape(T, -1, hd)
    C = _chunks(T)

    @jax.checkpoint
    def chunk(args):
        n, x, cs, sn = args
        q = _rotate(_mm(_rms(x, p[pre + "attn_norm/g"], eps),
                        p[pre + "attn/Wq"], precision).reshape(C, -1, hd),
                    cs, sn)
        o = _mm(_attend(q, k, v, n * C, precision), p[pre + "attn/Wo"],
                precision)
        a = x + _rms(o, p[pre + "attn_post/g"], eps)
        f = _gated(_rms(a, p[pre + "mlp_norm/g"], eps), p[pre + "mlp/W1"],
                   p[pre + "mlp/W3"], p[pre + "mlp/W2"], precision)
        return a + _rms(f, p[pre + "mlp_post/g"], eps)

    cut = lambda x: x.reshape((T // C, C) + x.shape[1:])
    out = jax.lax.map(chunk, (jnp.arange(T // C), cut(h), cut(cos), cut(sin)))
    return out.reshape(T, -1)


def hidden(p, ids, cfg, precision="float32"):
    """``[B, T]`` token ids -> ``[R, B, T, hidden]``: every pass's hidden
    state after the final norm. The passes and the layers are written out;
    each (pass, layer, sequence) is recomputed whole in the backward pass."""
    x = p["embed/W"][ids]
    passes = []
    for _ in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            own = {k: v for k, v in p.items() if k.startswith(f"l{i}.")}
            one = jax.checkpoint(
                lambda row, own=own, i=i: layer(own, i, row, cfg, precision))
            x = jax.lax.map(one, x)
        x = _rms(x, p["norm/g"], cfg["rms_norm_eps"])
        passes.append(x)
    return jnp.stack(passes)


def logits(p, ids, cfg, precision="float32"):
    """``[B, T]`` token ids -> the last pass's ``[B, T, vocabulary]`` float32
    logits (``early_exit_threshold`` 1: inference stops at no earlier
    pass)."""
    return _mm(hidden(p, ids, cfg, precision)[-1], p["lm_head/W"], precision)


def exit_gates(p, h, precision="float32"):
    """``h`` ``[R, ..., hidden]`` -> ``g`` ``[R, ...]``."""
    return jax.nn.sigmoid(_mm(h, p["lm_head/Wg"], precision)[..., 0]
                          + p["lm_head/bg"][0])


def exit_distribution(g):
    """``g`` ``[R, ...]`` -> ``p`` ``[R, ...]``: the probability of leaving
    after pass ``t``; the last pass takes what is left."""
    stay = jnp.cumprod(1.0 - g, axis=0)              # prod_{j<=t} (1 - g(j))
    before = jnp.concatenate([jnp.ones_like(g[:1]), stay[:-1]])
    return jnp.concatenate([(g * before)[:-1], before[-1:]])


def cross_entropies(p, h, ids, precision="float32"):
    """``h`` ``[R, B, T, hidden]`` -> ``ce`` ``[R, B, T]`` of position ``i``
    against token ``i + 1`` (each row's last position reads the row's first
    token and is masked by the caller), a chunk of positions at a time."""
    R, B, T, d = h.shape
    C = _chunks(T)
    nxt = jnp.broadcast_to(jnp.roll(ids, -1, axis=1), (R, B, T))

    @jax.checkpoint
    def chunk_nll(args):
        x, y = args
        z = _mm(x, p["lm_head/W"], precision)
        picked = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(z, axis=-1) - picked

    ce = jax.lax.map(chunk_nll, (h.reshape(-1, C, d), nxt.reshape(-1, C)))
    return ce.reshape(R, B, T)


def loss(p, batch, cfg, precision="float32"):
    """The exit-weighted objective over the positions that have a next
    token; the labels are ``batch[0]`` shifted by one."""
    ids = jnp.asarray(batch[0], jnp.int32)
    B, T = ids.shape
    h = hidden(p, ids, cfg, precision)
    ce = cross_entropies(p, h, ids, precision)
    pr = exit_distribution(exit_gates(p, h, precision))
    entropy = -jnp.sum(pr * jnp.log(jnp.where(pr > 0, pr, 1.0)), axis=0)
    per_position = jnp.sum(pr * ce, axis=0) \
        - cfg["assumed"]["exit_beta"] * entropy
    scored = jnp.arange(T) < T - 1
    return jnp.sum(jnp.where(scored, per_position, 0.0)) / (B * (T - 1))
