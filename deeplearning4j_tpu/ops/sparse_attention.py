"""Attention whose open keys a learned indexer chooses at run time
(DeepSeek-V3.2-Exp's lightning indexer): the index scores and the selection
that turns them into the open-key mask of a layer.

``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` over ``Hi`` index heads
against ONE index key a position, in float32. Key ``s`` is open to query
``t`` where ``s <= t`` and ``I[t, s]`` is among the ``topk`` largest of the
row (all of the row where ``t + 1 <= topk``); exactly ``topk`` whatever the
ties, the lower key index first, as ``jax.lax.top_k`` breaks them. The mask
is data, one ``[B, T, T]`` boolean a layer, shared by every head:
``causal_attention(..., select=mask)`` reads it (on the chip the masked flash
kernels take it as an 8-bit operand, a tile at a time, once for the query
heads of a KV head). No gradient passes through the choice.

:func:`open_keys` walks the queries in blocks so that no ``[Hi, T, T]`` array
exists: the rows that lie within the first ``topk`` positions are the causal
triangle and make no scores; the others are grouped by how far their keys
reach (a multiple of ``span``) and each group is one ``lax.map`` over blocks
of ``block`` queries. The ``topk``-th largest score of a row is found by its
bits, four at a time (eight counting passes over the block, no sort; the
passes are a loop: written out they ran the cell 2.1% faster and compiled
its step in 93 s more, PERF.md, PR 41); where
a block has a row whose ``topk``-th and next scores are equal, and only
there, a running count of the tied keys opens the lowest indices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_RADIX_BITS = 4


def index_scores(q_idx, k_idx, w):
    """``q_idx`` ``[B, C, Hi, di]``, ``k_idx`` ``[B, S, di]``, ``w`` ``[B, C,
    Hi]`` -> ``I`` ``[B, C, S]`` float32: the products accumulate in float32
    whatever the operands' dtype, the ReLU and the weighted sum over the
    index heads are float32."""
    with jax.named_scope("attn.index"):
        dots = jnp.einsum("bchd,bsd->bhcs", q_idx, k_idx,
                          preferred_element_type=jnp.float32)
        wt = w.astype(jnp.float32).transpose(0, 2, 1)[..., None]
        return jnp.sum(jax.nn.relu(dots) * wt, axis=1)


def _sortable(x):
    """float32 -> uint32 that orders as the floats do (no finite float maps
    to 0, which stands for a key that may not be chosen)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = bits >> 31
    return jnp.where(sign == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(u, k: int):
    """``u`` ``[..., S]`` uint32 -> ``[..., 1]``: the largest value that at
    least ``k`` of the row reach, found ``_RADIX_BITS`` bits at a time: each
    pass counts the row against the 15 ways of extending the prefix and
    keeps the largest that ``k`` still reach."""
    ways = (1 << _RADIX_BITS) - 1
    passes = 32 // _RADIX_BITS

    def one_pass(n, prefix):
        shift = (jnp.uint32(passes - 1) - n.astype(jnp.uint32)) * _RADIX_BITS
        digit = jnp.zeros_like(prefix)
        for i in range(1, ways + 1):
            reach = jnp.sum(u >= (prefix | (jnp.uint32(i) << shift)), axis=-1,
                            keepdims=True, dtype=jnp.int32)
            digit = digit + (reach >= k).astype(jnp.uint32)
        return prefix | (digit << shift)

    return jax.lax.fori_loop(0, passes, one_pass,
                             jnp.zeros(u.shape[:-1] + (1,), jnp.uint32))


def _causal(q0, rows: int, keys: int):
    """``[rows, keys]`` bool: key ``s`` is no later than query ``q0 + r``."""
    t = q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 0)
    return jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1) <= t


def select(scores, topk: int, q0: int = 0):
    """``scores`` ``[B, C, S]`` float32 of the queries at positions ``q0 ..
    q0 + C - 1`` against keys ``0 .. S - 1`` -> (open ``[B, C, S]`` bool,
    rows ``[B, C]`` bool whose ``topk``-th and next scores are equal). A
    row with at most ``topk`` causal keys opens them all."""
    with jax.named_scope("attn.index"):
        B, C, S = scores.shape
        causal = _causal(q0, C, S)
        if topk >= S:
            return (jnp.broadcast_to(causal, (B, C, S)),
                    jnp.zeros((B, C), bool))
        u = jnp.where(causal, _sortable(scores), jnp.uint32(0))
        kth = _kth_largest(u, topk)
        above = u > kth
        at = u == kth
        n_above = jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
        n_at = jnp.sum(at, axis=-1, keepdims=True, dtype=jnp.int32)
        # a row with fewer than topk causal keys reads kth == 0: every causal
        # key lies above it and the keys "at" it are the closed ones
        tied = (n_above + n_at > topk) & (kth > 0)

        def lowest_tied(_):
            rank = jnp.cumsum(at, axis=-1, dtype=jnp.int32)
            return above | (at & (rank <= topk - n_above))

        open_ = jax.lax.cond(jnp.any(tied), lowest_tied,
                             lambda _: above | at, None)
        return open_ & causal, tied[..., 0]


def open_keys(q_idx, k_idx, w, topk: int, *, block: int = 256,
              span: int = 2048):
    """The open-key mask of a layer. ``q_idx`` ``[B, T, Hi, di]``, ``k_idx``
    ``[B, T, di]``, ``w`` ``[B, T, Hi]`` -> (mask ``[B, T, T]`` bool, open
    keys summed over the queries ``[]`` uint32, rows whose ``topk``-th and
    next scores are equal ``[]`` uint32; the counts wrap at 2**32).
    ``block`` queries are scored and selected at a time against keys ``0 ..
    reach``, ``reach`` the block's end rounded up to ``span`` (the ``[B, Hi,
    block, reach]`` products are the largest array there is; on a v5e at 2 x
    8,192 a layer's mask takes 10.8 ms at 256 queries, 11.1 at 512 and 12.8
    at 1,024: PERF.md, PR 41); a sequence that ``block`` does not divide is
    one block."""
    B, T = q_idx.shape[:2]
    q_idx, k_idx, w = (jax.lax.stop_gradient(a) for a in (q_idx, k_idx, w))
    if T <= block or T % block:
        mask, tied = select(index_scores(q_idx, k_idx, w), topk)
    else:
        pieces, tied = [], []
        reach = lambda r: min(T, -(-r // span) * span)
        r0 = 0
        while r0 < T:
            r1 = r0 + block
            while r1 < T and reach(r1 + block) == reach(r0 + block):
                r1 += block
            rows = _rows(q_idx, k_idx, w, topk, r0, r1, reach(r1), block)
            pieces.append(jnp.pad(rows[0],
                                  ((0, 0), (0, 0), (0, T - reach(r1)))))
            tied.append(rows[1])
            r0 = r1
        mask = jnp.concatenate(pieces, axis=1)
        tied = jnp.concatenate(tied, axis=1)
    with jax.named_scope("attn.index"):
        return (mask, jnp.sum(mask, dtype=jnp.uint32),
                jnp.sum(tied, dtype=jnp.uint32))


def _rows(q_idx, k_idx, w, topk, r0, r1, reach, block):
    """Queries ``r0 .. r1 - 1`` against keys ``0 .. reach - 1``, ``block`` at
    a time. Rows that all lie within the first ``topk`` positions are the
    causal triangle."""
    B = q_idx.shape[0]
    if r1 <= topk:
        return (jnp.broadcast_to(_causal(r0, r1 - r0, reach),
                                 (B, r1 - r0, reach)),
                jnp.zeros((B, r1 - r0), bool))
    n = (r1 - r0) // block
    keys = k_idx[:, :reach]

    def one(args):
        i, q, wt = args
        # the block's first position is data: select() adds it to an iota
        return select(index_scores(q, keys, wt), topk, r0 + i * block)

    cut = lambda a: a[:, r0:r1].reshape((B, n, block) + a.shape[2:]) \
        .swapaxes(0, 1)
    open_, tied = jax.lax.map(one, (jnp.arange(n), cut(q_idx), cut(w)))
    return (open_.swapaxes(0, 1).reshape(B, r1 - r0, reach),
            tied.swapaxes(0, 1).reshape(B, r1 - r0))
