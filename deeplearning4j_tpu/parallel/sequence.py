"""Sequence/context parallelism: ring attention over a device mesh.

BEYOND-PARITY long-context support (SURVEY.md §2.7 records the reference's
only long-sequence mechanism as truncated BPTT; §5 marks ring/blockwise
attention "explicitly stretch"). The build brief makes long context
first-class, so this module provides the TPU-native mechanism: the sequence
axis is sharded across the mesh, each device holds its Q shard plus a
rotating K/V block, and blocks circulate over ICI via ``lax.ppermute``
while an online-softmax accumulator (the flash-attention recurrence)
combines partial results — attention over sequences ~mesh_size× longer
than one device's HBM could hold, with compute/communication overlap left
to XLA's latency hiding.

Layout: [B, H, T, D] with T sharded on the ``sp`` mesh axis. Causal masking
uses global position offsets carried alongside each rotating block.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.math import precision_for


def _block_attention(q, k, v, m, l, o, q_pos, k_pos, causal, key_mask):
    """One flash-accumulation step against a single K/V block.

    q [B,H,Tq,D]; k,v [B,H,Tb,D]; m,l [B,H,Tq]; o [B,H,Tq,D];
    q_pos [Tq], k_pos [Tb] global positions; key_mask [B,Tb] keep-mask.
    """
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=precision_for(q, k)) * scale
    # -inf (not finfo.min): the isfinite guards below detect fully-masked
    # rows only if masked scores are genuinely non-finite
    neg = jnp.asarray(-jnp.inf, s.dtype)
    if causal:
        allow = q_pos[:, None] >= k_pos[None, :]          # [Tq, Tb]
        s = jnp.where(allow[None, None], s, neg)
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :] > 0, s, neg)
    blk_max = jnp.max(s, axis=-1)                          # [B,H,Tq]
    m_new = jnp.maximum(m, blk_max)
    # fully-masked rows keep m = -inf; exp(neg - neg) would NaN, so clamp
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    correction = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * correction + jnp.sum(p, axis=-1)
    o_new = o * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v, precision=precision_for(p, v))
    return m_new, l_new, o_new


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = False, key_mask=None):
    """Exact attention with K/V rotating around the mesh ring.

    Args: q/k/v [B, H, T, D] GLOBAL arrays with T sharded over ``axis``
    (replicated inputs are resharded); optional ``key_mask`` [B, T]
    keep-mask sharded the same way. Returns [B, H, T, D] sharded like q.

    Each of the ``p`` ring steps attends q's local shard against one K/V
    block, then ppermutes the block (and its global offset) to the next
    device — per-device peak memory O(T/p), total traffic (p-1)/p of K+V
    over ICI, and the result is EXACT (online softmax), not an
    approximation.
    """
    n = mesh.shape[axis]
    t_total = q.shape[2]
    if t_total % n:
        raise ValueError(f"sequence length {t_total} not divisible by "
                         f"mesh axis {axis}={n}")

    spec_qkv = P(None, None, axis, None)
    spec_mask = P(None, axis)

    def local_fn(q_l, k_l, v_l, mask_l):
        idx = jax.lax.axis_index(axis)
        t_loc = q_l.shape[2]
        q_pos = idx * t_loc + jnp.arange(t_loc)
        B, H, Tq, D = q_l.shape
        m = jnp.full((B, H, Tq), -jnp.inf, q_l.dtype)
        l = jnp.zeros((B, H, Tq), q_l.dtype)
        o = jnp.zeros_like(q_l)

        def body(i, carry):
            m, l, o, k_blk, v_blk, blk_idx, mask_blk = carry
            k_pos = blk_idx * t_loc + jnp.arange(t_loc)
            m, l, o = _block_attention(q_l, k_blk, v_blk, m, l, o,
                                       q_pos, k_pos, causal, mask_blk)
            perm = [(j, (j + 1) % n) for j in range(n)]
            k_blk = jax.lax.ppermute(k_blk, axis, perm)
            v_blk = jax.lax.ppermute(v_blk, axis, perm)
            blk_idx = jax.lax.ppermute(blk_idx, axis, perm)
            if mask_blk is not None:
                mask_blk = jax.lax.ppermute(mask_blk, axis, perm)
            return m, l, o, k_blk, v_blk, blk_idx, mask_blk

        carry = (m, l, o, k_l, v_l, idx, mask_l)
        for i in range(n):  # unrolled: n is a small static mesh dim
            carry = body(i, carry)
        m, l, o = carry[0], carry[1], carry[2]
        return o / jnp.maximum(l, 1e-30)[..., None]

    if key_mask is None:
        fn = jax.shard_map(lambda a, b, c: local_fn(a, b, c, None),
                           mesh=mesh,
                           in_specs=(spec_qkv, spec_qkv, spec_qkv),
                           out_specs=spec_qkv)
        return fn(q, k, v)
    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_mask),
                       out_specs=spec_qkv)
    return fn(q, k, v, key_mask)


def sequence_sharded(x, mesh: Mesh, axis: str = "sp", time_axis: int = 2):
    """Place an array with its time dimension sharded over the mesh axis."""
    spec = [None] * x.ndim
    spec[time_axis] = axis
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))


def make_sp_mesh(devices=None, axis: str = "sp") -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devs), (axis,))
