"""Plain Qwen3-Next-80B-A3B decoder with its next-token loss: one chip's
share.

The benchmark's reference for the ``qwen3_next_80b_a3b`` configuration (Qwen
Qwen3-Next-80B-A3B-Instruct ``config.json``), in straightforward
``jax.numpy`` at float32 / ``highest``. It imports nothing from the program
under test and takes nothing the program made. Layer ``i`` is a full layer
where ``(i + 1) % full_attention_interval == 0``, else a Gated DeltaNet
layer; with ``h`` ``[T, hidden]``:

1. ``a = RMSNorm_in(h)``.
2. Gated DeltaNet (Yang et al., arXiv:2412.06464, as the published module
   lays it out): ``a in_proj_qkvz`` gives, for each of the 16 key heads in
   turn, ``q``, ``k`` (128 each) and the ``v`` and ``z`` of its 2 value heads
   (2 x 128 each); ``a in_proj_ba`` gives each key head's 2 ``b`` then 2
   ``a``. ``[q, k, v]`` over all heads (8,192 channels) goes through a causal
   depthwise convolution of 4 positions (``conv1d[c, j]`` weighs position ``t
   - 3 + j``) and SiLU. ``q`` and ``k`` are divided by ``sqrt(sum x^2 +
   1e-6)``, ``q`` times ``128^-1/2``, and key head ``j`` serves value heads
   ``2j`` and ``2j + 1``. ``beta = sigmoid(b)``, ``g = -exp(A_log)
   softplus(a + dt_bias)``. Each value head's state ``S`` ``[128, 128]``
   starts at zero and, one position at a time, ``S = e^g S; S = S + k
   (beta (v - S^T k))^T; o = S^T q``. Then ``o`` is RMS-normed over each
   head's 128 channels (gain ``norm``), times ``silu(z)``, and ``out_proj``.
3. Gated full attention: ``q = a Wq``, ``gate = a Wg`` as ``[T, 16, 256]``,
   ``k = a Wk``, ``v = a Wv`` as ``[T, 2, 256]``; ``q`` and ``k`` RMS-normed
   over each head's 256 channels (gains ``gq``, ``gk``); the first 64
   channels rotated (channel ``j`` with ``j + 32``, angle ``t /
   1e7^(2j / 64)``); query head ``i`` reads KV head ``i // 8``; ``o =
   softmax(q k^T / 16 + causal) v``, times ``sigmoid(gate)``, then ``Wo``.
4. ``h = h + mixer``; ``m = RMSNorm_post(h)``; the router: ``p = softmax(m
   Wr)`` over all 512 experts in float32, the 10 largest, ``w_e = p_e / sum(p
   over the chosen)``; ``h = h + sum over the chosen experts held here of w_e
   (silu(m W1_e) * (m W3_e)) W2_e + sigmoid(m Wsg) (silu(m S1) * (m S3))
   S2``. With ``deployment.absent`` ``stand_in`` the held ``w_e`` of a token
   are renormalised over themselves.
5. Final RMSNorm, untied head, mean cross-entropy of position ``t`` against
   token ``t + 1`` over the positions that have a next token.

Departures from the published model, all stated in
``configs/qwen3_next_80b_a3b.json``: four of 48 layers (one period); of 512
experts the 16 from ``deployment.held``, which stand in for the others a
token chose; the vocabulary's first eighth; the norms' gains start at 1 where
the published ``(1 + w)`` starts ``w`` at 0; the query's gate is its own
matrix ``Wg`` where the published ``q_proj`` interleaves it with the query;
the initialisers are assumed; labels are the inputs shifted by one.

Computed so that 32,768 tokens fit beside the optimizer's state: a layer at
a time, a sequence at a time (each recomputed whole in the backward pass),
and inside it blocks of 128 positions, each recomputed in the backward pass.
A full layer's keys and values come first for the whole sequence, then each
block attends over the keys its mask leaves open. A Gated DeltaNet layer
walks its blocks in order, carrying the state and the convolution's last
three inputs from one to the next, and inside a block walks its positions
one at a time. Every held expert runs over every token of a block with the
weights of the tokens that did not choose it at nought.

Leaves are named ``<vertex>/<param>`` as the program's graph names them;
matrices are ``[in, out]``. ``precision`` is ``common.round_operand``'s: it
rounds the operands of every product with a weight and of the attention's
two products; the recurrence's products with the state stay float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import (HI as _HI, make_weights, round_operand as _round,
                     seed_key)

_BLOCK = 128


def is_full(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] == 0


def layer_table(cfg):
    """[(leaf name, shape, init)] in a fixed order; each ``A_log`` is made
    apart (``init_weights``) and listed with ``"log_uniform_16"``."""
    d, heads, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
    hd, f = cfg["head_dim"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    std = ("normal", cfg["assumed"]["initializer_std"])
    held = cfg["deployment"]["held"][1]
    routed = cfg["deployment"]["num_experts_routed"]
    out = [("embed/W", (cfg["vocab_size"], d),
            ("normal", cfg["assumed"]["embedding_std"]))]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        out.append((p + "attn_norm/g", (d,), "ones"))
        if is_full(cfg, i):
            out += [(p + "attn/Wq", (d, heads * hd), std),
                    (p + "attn/Wk", (d, kv * hd), std),
                    (p + "attn/Wv", (d, kv * hd), std),
                    (p + "attn/Wo", (heads * hd, d), std),
                    (p + "attn/Wg", (d, heads * hd), std),
                    (p + "attn/gq", (hd,), "ones"),
                    (p + "attn/gk", (hd,), "ones")]
        else:
            out += [(p + "attn/in_proj_qkvz", (d, 2 * hk * dk + 2 * hv * dv),
                     std),
                    (p + "attn/in_proj_ba", (d, 2 * hv), std),
                    (p + "attn/conv1d", (2 * hk * dk + hv * dv,
                                         cfg["linear_conv_kernel_dim"]), std),
                    (p + "attn/A_log", (hv,), "log_uniform_16"),
                    (p + "attn/dt_bias", (hv,), "ones"),
                    (p + "attn/norm", (dv,), "ones"),
                    (p + "attn/out_proj", (hv * dv, d), std)]
        out += [(p + "mlp_norm/g", (d,), "ones"),
                (p + "mlp/Wr", (d, routed), std),
                (p + "mlp/W1", (held, d, f), std),
                (p + "mlp/W3", (held, d, f), std),
                (p + "mlp/W2", (held, f, d), std),
                (p + "mlp/S1", (d, fs), std),
                (p + "mlp/S3", (d, fs), std),
                (p + "mlp/S2", (fs, d), std),
                (p + "mlp/Wsg", (d, 1), std)]
    out += [("norm/g", (d,), "ones"),
            ("lm_head/W", (d, cfg["vocab_size"]), std)]
    return out


def init_weights(seed: int, cfg) -> dict:
    """All float32 master weights, made on the device. ``A_log`` is ``log
    U(0, 16]``, drawn from its own fold of the seed's key."""
    table = layer_table(cfg)
    w = make_weights([t if t[2] != "log_uniform_16" else (t[0], t[1], "zeros")
                      for t in table], seed)

    @jax.jit
    def log_uniform(key):
        return {name: jnp.log(16.0 * (1.0 - jax.random.uniform(
            jax.random.fold_in(key, 1_000_000 + i), shape, jnp.float32)))
            for i, (name, shape, init) in enumerate(table)
            if init == "log_uniform_16"}

    return dict(w, **log_uniform(seed_key(seed)))


def _mm(x, w, precision):
    return jnp.einsum("...i,io->...o", _round(x, precision),
                      _round(w, precision), precision=_HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _experts(p, pre, m, cfg, precision):
    """The routed experts held here and the gated shared expert over the
    block ``m`` ``[C, hidden]``."""
    first, held = cfg["deployment"]["held"]
    probs = jax.nn.softmax(_mm(m, p[pre + "Wr"], precision), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    w = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    here = first + jnp.arange(held)
    w_e = jnp.sum(jnp.where(top_e[:, :, None] == here, w[:, :, None], 0.0),
                  axis=1)                                        # [C, held]
    if cfg["deployment"]["absent"] == "stand_in":
        total = jnp.sum(w_e, axis=1, keepdims=True)
        w_e = w_e / jnp.where(total > 0, total, 1.0)
    d, f = p[pre + "W1"].shape[1:]
    w1 = p[pre + "W1"].transpose(1, 0, 2).reshape(d, held * f)
    w3 = p[pre + "W3"].transpose(1, 0, 2).reshape(d, held * f)
    hidden = jax.nn.silu(_mm(m, w1, precision)) * _mm(m, w3, precision)
    routed = _mm(hidden * jnp.repeat(w_e, f, axis=1),
                 p[pre + "W2"].reshape(held * f, d), precision)
    shared = _mm(jax.nn.silu(_mm(m, p[pre + "S1"], precision))
                 * _mm(m, p[pre + "S3"], precision), p[pre + "S2"], precision)
    return routed + jax.nn.sigmoid(_mm(m, p[pre + "Wsg"], precision)) * shared


def _rotate(x, cos, sin):
    """``x`` ``[C, heads, d]``: the first ``2 * cos.shape[-1]`` channels
    rotated, channel ``j`` with ``j + cos.shape[-1]``; the rest as they
    are."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def _full_layer(p, pre, h, cfg, precision):
    """One sequence ``[T, hidden]`` through a gated full-attention layer and
    its experts, a block of positions at a time after the keys and
    values."""
    T, eps = h.shape[0], cfg["rms_norm_eps"]
    hd, kv = cfg["head_dim"], cfg["num_key_value_heads"]
    rot = int(hd * cfg["partial_rotary_factor"])
    inv = 1.0 / (float(cfg["rope_theta"])
                 ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    a = _rms(h, p[pre + "attn_norm/g"], eps)
    k = _rotate(_rms(_mm(a, p[pre + "attn/Wk"], precision).reshape(T, kv, hd),
                     p[pre + "attn/gk"], eps), cos, sin)
    v = _mm(a, p[pre + "attn/Wv"], precision).reshape(T, kv, hd)
    C = _BLOCK if T % _BLOCK == 0 else T

    @jax.checkpoint
    def block(args):
        n, hc, cs, sn = args
        ac = _rms(hc, p[pre + "attn_norm/g"], eps)
        q = _rotate(_rms(_mm(ac, p[pre + "attn/Wq"], precision).reshape(
            C, -1, hd), p[pre + "attn/gq"], eps), cs, sn)
        H = q.shape[1]
        qg = q.reshape(C, kv, H // kv, hd)
        s = jnp.einsum("qhgd,khd->hgqk", _round(qg, precision),
                       _round(k, precision), precision=_HI) / math.sqrt(hd)
        qi = n * C + jnp.arange(C)[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= qi, s, -jnp.inf)
        # softmax written out, the row's maximum and sum behind a barrier:
        # left to itself the TPU compiler makes a row-wide reduce-window
        mx = jax.lax.optimization_barrier(
            jnp.max(jax.lax.stop_gradient(s), axis=-1, keepdims=True))
        e = jnp.exp(s - mx)
        pr = e / jax.lax.optimization_barrier(jnp.sum(e, axis=-1,
                                                      keepdims=True))
        o = jnp.einsum("hgqk,khd->qhgd", _round(pr, precision),
                       _round(v, precision), precision=_HI).reshape(C, H * hd)
        o = o * jax.nn.sigmoid(_mm(ac, p[pre + "attn/Wg"], precision))
        hc = hc + _mm(o, p[pre + "attn/Wo"], precision)
        m = _rms(hc, p[pre + "mlp_norm/g"], eps)
        return hc + _experts(p, pre + "mlp/", m, cfg, precision)

    cut = lambda x: x.reshape((T // C, C) + x.shape[1:])
    out = jax.lax.map(block, (jnp.arange(T // C), cut(h), cut(cos), cut(sin)))
    return out.reshape(T, -1)


def _recurrence(S, q, k, v, g, beta):
    """The gated delta rule one position at a time from the state ``S``
    ``[Hv, dk, dv]``; ``q``, ``k`` ``[C, Hv, dk]``, ``v`` ``[C, Hv, dv]``,
    ``g``, ``beta`` ``[C, Hv]``. -> (the state after, ``o`` ``[C, Hv,
    dv]``)."""
    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[:, None, None] * S
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt,
                                           precision=_HI))
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=_HI)
    return jax.lax.scan(step, S, (q, k, v, g, beta))


def _linear_layer(p, pre, h, cfg, precision):
    """One sequence ``[T, hidden]`` through a Gated DeltaNet layer and its
    experts, a block of positions at a time in order, carrying the state
    and the convolution's last inputs."""
    T, eps = h.shape[0], cfg["rms_norm_eps"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K, r = cfg["linear_conv_kernel_dim"], hv // hk
    conv_w = p[pre + "attn/conv1d"]
    C = _BLOCK if T % _BLOCK == 0 else T

    @jax.checkpoint
    def block(carry, hc):
        S, tail = carry
        a = _rms(hc, p[pre + "attn_norm/g"], eps)
        qkvz = _mm(a, p[pre + "attn/in_proj_qkvz"], precision).reshape(
            C, hk, 2 * dk + 2 * r * dv)
        ba = _mm(a, p[pre + "attn/in_proj_ba"], precision).reshape(C, hk,
                                                                    2 * r)
        mixed = jnp.concatenate(
            [qkvz[..., :dk].reshape(C, hk * dk),
             qkvz[..., dk:2 * dk].reshape(C, hk * dk),
             qkvz[..., 2 * dk:2 * dk + r * dv].reshape(C, hv * dv)], axis=-1)
        z = qkvz[..., 2 * dk + r * dv:].reshape(C, hv, dv)
        seen = jnp.concatenate([tail, mixed])            # [K - 1 + C, ch]
        conv = sum(seen[j:j + C] * conv_w[:, j] for j in range(K))
        x = jax.nn.silu(conv)
        norm = lambda t: t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                      + 1e-6)
        q = norm(x[:, :hk * dk].reshape(C, hk, dk)) / math.sqrt(dk)
        k = norm(x[:, hk * dk:2 * hk * dk].reshape(C, hk, dk))
        v = x[:, 2 * hk * dk:].reshape(C, hv, dv)
        q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
        beta = jax.nn.sigmoid(ba[..., :r].reshape(C, hv))
        g = -jnp.exp(p[pre + "attn/A_log"]) * jax.nn.softplus(
            ba[..., r:].reshape(C, hv) + p[pre + "attn/dt_bias"])
        S, o = _recurrence(S, q, k, v, g, beta)
        o = _rms(o, p[pre + "attn/norm"], eps) * jax.nn.silu(z)
        hc = hc + _mm(o.reshape(C, hv * dv), p[pre + "attn/out_proj"],
                      precision)
        m = _rms(hc, p[pre + "mlp_norm/g"], eps)
        return (S, seen[C:]), hc + _experts(p, pre + "mlp/", m, cfg,
                                            precision)

    start = (jnp.zeros((hv, dk, dv), h.dtype),
             jnp.zeros((K - 1, 2 * hk * dk + hv * dv), h.dtype))
    _, out = jax.lax.scan(block, start, h.reshape(T // C, C, -1))
    return out.reshape(T, -1)


def hidden(p, ids, cfg, precision="float32"):
    """``[B, T]`` token ids -> ``[B, T, hidden]`` after the final norm: a
    layer at a time over the batch, a sequence at a time inside it, each
    (layer, sequence) recomputed whole in the backward pass."""
    h = p["embed/W"][ids]
    for i in range(cfg["num_hidden_layers"]):
        pre = f"l{i}."
        own = {k: v for k, v in p.items() if k.startswith(pre)}
        kind = _full_layer if is_full(cfg, i) else _linear_layer
        one = jax.checkpoint(
            lambda own, row, pre=pre, kind=kind: kind(own, pre, row, cfg,
                                                      precision))
        h = jnp.stack([one(own, row) for row in h])
    return _rms(h, p["norm/g"], cfg["rms_norm_eps"])


def loss(p, batch, cfg, precision="float32"):
    """Mean next-token cross-entropy over the positions that have a next
    token; the labels are ``batch[0]`` shifted by one. The head, like the
    layers, takes a block of positions at a time."""
    ids = jnp.asarray(batch[0], jnp.int32)
    B, T = ids.shape
    C = _BLOCK if T % _BLOCK == 0 else T
    nxt = jnp.roll(ids, -1, axis=1)
    scored = jnp.broadcast_to(jnp.arange(T) < T - 1, (B, T))

    @jax.checkpoint
    def block_nll(args):
        h, y, m = args
        lg = _mm(h, p["lm_head/W"], precision)
        picked = jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(lg, axis=-1) - picked) * m)

    cut = lambda x: x.reshape((B * T // C, C) + x.shape[2:])
    total = jnp.sum(jax.lax.map(
        block_nll, (cut(hidden(p, ids, cfg, precision)), cut(nxt),
                    cut(scored))))
    return total / (B * (T - 1))
