"""Causal and sliding-window attention over grouped KV heads without a
``[T, T]`` score matrix, and the rotary embeddings that go with it.

A blocked XLA path, not a kernel: queries are cut into blocks, each block
sees only the key blocks its mask leaves open (whole masked blocks are never
computed), and every block is a ``jax.checkpoint`` so the backward pass holds
one block's scores at a time. ``ops/flash_attention.py`` is left as it is:
its kernels know neither a causal nor a window mask nor grouped KV heads,
and the encoder path that runs on them must not move.

Layout: ``q`` ``[B, T, H, d]``, ``k`` ``[B, T, KV, d]``, ``v`` ``[B, T, KV,
dv]``; query head ``i`` reads KV head ``i // (H // KV)``. ``dv`` need not be
``d`` (latent attention scores over 192 and carries values of 128).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import telemetry as _tel

_NEG = -1e30

_DISPATCH = _tel.counter(
    "attention.dispatch",
    "causal attention sites by mask kind and the path taken, once a traced "
    "site")


# ------------------------------------------------------------------- rotary
def default_inv_freq(rot_dim: int, theta: float) -> np.ndarray:
    """``1 / theta^(2i / rot_dim)`` for the ``rot_dim / 2`` pairs."""
    return 1.0 / (float(theta) ** (np.arange(0, rot_dim, 2, dtype=np.float64)
                                   / rot_dim))


def yarn_inv_freq(rot_dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """YaRN's frequencies (Peng et al., arXiv:2309.00071) as HF's
    ``_compute_yarn_parameters`` computes them: pairs that turn more than
    ``beta_fast`` times inside the original context keep their frequency,
    pairs that turn fewer than ``beta_slow`` times are slowed by ``factor``,
    a linear ramp between."""
    def correction_dim(rotations):
        return (rot_dim * math.log(original_max_position
                                   / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rot_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = default_inv_freq(rot_dim, theta)
    interpolation = extrapolation / factor
    keep = 1.0 - ramp
    return interpolation * (1.0 - keep) + extrapolation * keep


def rotary_tables(positions: int, inv_freq: np.ndarray,
                  attention_factor: float = 1.0):
    """-> (cos, sin), float32 ``[positions, rot_dim / 2]``, each already
    times ``attention_factor``."""
    ang = np.arange(positions, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(ang) * attention_factor, jnp.float32),
            jnp.asarray(np.sin(ang) * attention_factor, jnp.float32))


def apply_rotary(x, cos, sin):
    """Rotate the first ``2 * cos.shape[-1]`` of ``x``'s last axis, pairing
    dimension ``i`` with ``i + rot_dim / 2`` (HF's ``rotate_half``); the rest
    passes through. ``x`` ``[B, T, heads, d]``, in float32, back in ``x``'s
    dtype."""
    half = cos.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :half], xf[..., half:2 * half], xf[..., 2 * half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)
    return out.astype(x.dtype)


def deinterleave(x):
    """The last axis' pairs ``(2i, 2i + 1)`` moved to ``(i, i + n / 2)``, the
    order :func:`apply_rotary` pairs: how HF's DeepSeek-V3 reads
    ``rope_interleave``. Scores do not depend on the channel order as long
    as queries and keys share it."""
    n = x.shape[-1]
    return x.reshape(x.shape[:-1] + (n // 2, 2)).swapaxes(-1, -2) \
        .reshape(x.shape)


# ---------------------------------------------------------------- attention
def _softmax(s):
    """Softmax over the last axis with the row's maximum and sum held
    behind an optimisation barrier. Left to itself the TPU compiler turns
    ``reduce -> broadcast -> subtract`` into a ``reduce-window`` as wide as
    the row, quadratic in the keys: 17.6 ms for a ``[6, 1024, 8192]`` block
    that the memory system moves in half a millisecond (PERF.md, PR 31)."""
    barrier = jax.lax.optimization_barrier
    m = barrier(jnp.max(jax.lax.stop_gradient(s), axis=-1, keepdims=True))
    e = jnp.exp(s - m)
    return e / barrier(jnp.sum(e, axis=-1, keepdims=True))


def _block(q, k, v, q0: int, k0: int, window: Optional[int]):
    """One query block against the keys its mask leaves open. ``q``
    ``[..., G, bq, d]``, ``k`` / ``v`` ``[..., nk, d]``; ``q0`` / ``k0`` the
    position of the first query / key (``k0`` may be negative: keys before
    position 0 are padding)."""
    d = q.shape[-1]
    s = jnp.einsum("...gqd,...kd->...gqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    qpos = q0 + jnp.arange(q.shape[-2])[:, None]
    kpos = k0 + jnp.arange(k.shape[-2])[None, :]
    open_ = (kpos <= qpos) & (kpos >= 0)
    if window is not None:
        open_ &= kpos > qpos - window
    p = _softmax(jnp.where(open_, s, _NEG))
    return jnp.einsum("...gqk,...kd->...gqd", p.astype(v.dtype), v)


def _rows_full(q, k, v, block: int, window: Optional[int],
               cut_inside: bool = False):
    """``q`` ``[G, T, d]``, ``k`` / ``v`` ``[T, d]``: query blocks one after
    another, each over the keys from the first block its mask reaches to its
    own. What a block's ``jax.checkpoint`` is handed is what the backward
    pass keeps of it: its own slices of the row or, with ``cut_inside``, the
    whole row, cut inside the checkpoint. Slices are new arrays, kept once a
    block (4.5x the row at 8 causal blocks); the whole row is the caller's
    own array and is kept once."""
    T = q.shape[1]
    outs = []
    for q0 in range(0, T, block):
        k0 = 0 if window is None else \
            max(0, (q0 - window + 1) // block * block)
        k1 = q0 + block
        if cut_inside:
            fn = jax.checkpoint(
                lambda a, b, c, q0=q0, k0=k0, k1=k1: _block(
                    a[:, q0:k1], b[k0:k1], c[k0:k1], q0, k0, window))
            outs.append(fn(q, k, v))
        else:
            fn = jax.checkpoint(
                lambda a, b, c, q0=q0, k0=k0: _block(a, b, c, q0, k0, window))
            outs.append(fn(q[:, q0:k1], k[k0:k1], v[k0:k1]))
    return jnp.concatenate(outs, axis=1)


def _rows_window(q, k, v, block: int, window: int):
    """The same for a window no longer than a block: every query block sees
    its own key block and the one before, so all blocks go through one
    batched product."""
    G, T, d = q.shape
    nb = T // block

    def pair(a):
        a = a.reshape(nb, block, a.shape[-1])
        before = jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]], axis=0)
        return jnp.concatenate([before, a], axis=1)            # [nb, 2b, d]

    qb = q.reshape(G, nb, block, d).transpose(1, 0, 2, 3)      # [nb, G, b, d]

    def one(qi, ki, vi, i):
        # the pair starts one block before the queries; block 0's first half
        # lies before position 0 and is padding
        return _block(qi, ki, vi, i * block, (i - 1) * block, window)

    out = jax.checkpoint(jax.vmap(one))(qb, pair(k), pair(v), jnp.arange(nb))
    return out.transpose(1, 0, 2, 3).reshape(G, T, v.shape[-1])


def causal_attention(q, k, v, *, window: Optional[int] = None,
                     block: int = 1024, kind: Optional[str] = None):
    """softmax(q k^T / sqrt(d) + mask) v with a causal mask and, with
    ``window``, key ``j`` open to query ``i`` only where ``i - window < j <=
    i``. -> ``[B, T, H, dv]``. A sequence no longer than ``block`` (or not a
    multiple of it) is one block. ``kind`` names the site in the
    ``attention.dispatch`` counter and the ``attn.<kind>`` scope (default:
    ``full`` or ``window``, by the mask)."""
    B, T, H, d = q.shape
    KV, dv = k.shape[2], v.shape[-1]
    if H % KV:
        raise ValueError(f"{H} query heads do not divide over {KV} KV heads")
    G = H // KV
    kind = kind or ("full" if window is None else "window")
    if window is not None:
        block = min(block, max(window, 128))
    with jax.named_scope(f"attn.{kind}"):
        qg = q.reshape(B, T, KV, G, d).transpose(0, 2, 3, 1, 4)  # [B,KV,G,T,d]
        kg, vg = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # [B,KV,T,d]
        if T <= block or T % block:
            _DISPATCH.inc(kind=kind, decision="one_block")
            out = _block(qg, kg, vg, 0, 0, window)
        else:
            if window is not None and window <= block:
                _DISPATCH.inc(kind=kind, decision="blocked_pairs")
                rows = lambda a: _rows_window(*a, block, window)
            else:
                # one query head a KV head (latent attention's expanded
                # keys): as many rows as heads, and each row's key and value
                # slices kept again for every block would be 1.5 GB a layer
                # at 32 heads of 192 + 128 over 8,192 positions. PROVISIONAL:
                # `G == 1` only keeps the grouped-head cell's program what it
                # was when the whole-row form came; nobody has measured that
                # form for G > 1 (ROADMAP R4: try it for all G first, and
                # delete the slice form and `cut_inside` if memory falls and
                # time holds)
                _DISPATCH.inc(kind=kind, decision="blocked_rows")
                rows = lambda a: _rows_full(*a, block, window, G == 1)
            flat = lambda a: a.reshape((B * KV,) + a.shape[2:])
            out = jax.lax.map(rows, (flat(qg), flat(kg), flat(vg)))
            out = out.reshape(B, KV, G, T, dv)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, dv)
