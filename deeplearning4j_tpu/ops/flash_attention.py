"""Tiled Pallas TPU flash attention + the framework's attention dispatch.

Attention was the one hot path the kernel layer had not touched:
``nn/layers/attention.py`` materialized the full [B,H,Tq,Tk] score matrix
through einsum+softmax, and the TF-imported BERT path runs the same
``batch_matmul -> scale -> mask-add -> softmax -> batch_matmul`` chain
through ``autodiff/samediff.py``. XLA fuses the softmax *chain* but still
round-trips the quadratic scores tensor through HBM in both forward and
backward — the exact fusion the TVM line of work (PAPERS.md) says must be
done by hand. This module is that hand fusion:

- :func:`flash_attention` — the raw fused op, in one of two tilings that
  :func:`default_blocks` chooses from the shape (the largest tile that
  fits VMEM; PERF.md section 6, PR 32, has the chip's readings):

  * **whole row** (a query block's whole key row in one tile; up to 4,096
    keys of d = 64 in bf16): a plain softmax in the forward, and ONE
    backward kernel that works the softmax out again from the tile, forms
    p and ds once and writes dk, dv and dq. Nothing is saved for it but the
    operands: no output, no statistics. Short rows take several heads a
    grid step (:func:`heads_per_step`).
  * **blocked** (a longer row): online-softmax forward over a
    (batch*heads, q-blocks, kv-blocks) grid with f32 running max/sum
    accumulators in VMEM scratch; kv is the innermost ("arbitrary") grid
    dimension so the scores tile never leaves VMEM. The backward
    recomputes p = exp(s - m)/l per tile (two kernels: dq, and dk/dv),
    saving only the per-row logsumexp — carried as its two pieces (running
    max m, running sum l) so a finfo.min mask bias can't absorb log(l) —
    plus the output, for di = sum(o*do). The forward and dq bodies also run
    under a static :class:`BlockMask` (causal, or causal with a window) for
    ``ops/causal_attention.py``, with a dk/dv body of their own that sums
    over the query heads of a KV head: a block pair the mask closes is
    skipped, and the statistics leave as one compact logsumexp row. With a
    mask that is data as one more 8-bit operand (``stacked``) all three
    read its tile beside the key block, and the query heads of a KV head
    are stacked in one tile so that the tile is fetched once for them.
- :func:`reference_attention` — the quadratic einsum path, scores upcast to
  f32 before softmax (matching the kernel's f32 accumulators; this is also
  the numerics fix for the layers' bf16 dtype policy).
- :func:`attention` — the dispatcher the layers and the SameDiff fused op
  ride: routes to the kernel on TPU (or in Pallas interpret mode when
  forced, so the CPU tier-1 suite exercises the real kernel code) when the
  shapes tile and the bias is key-reducible, else falls back to the
  reference path. Every routing decision bumps a counter
  (:func:`counters`) so a silent fallback is visible in tests and bench.

Numerics contract (kernel == reference at f32 atol ~1e-5): s = (q . k^T) *
scale + bias computed in f32; softmax in f32; p cast to the value dtype for
the p@v matmul with f32 accumulation; output cast back to the input dtype.
A fully-masked row (all keys at finfo.min bias) degrades to UNIFORM
attention in both paths — softmax of equal scores — preserving the layer
contract where masked *steps* are zeroed by the caller, not here.

Divergence (recorded in PARITY.md): the fused path treats ``bias`` as
non-differentiable (zero cotangent) — bias here is always a mask-derived
constant (layers' key masks, BERT's extended attention mask). A *learned*
additive bias must use the reference path (mode "off" or a non-key-
reducible bias, which falls back automatically).

LSTM-cell precedent and the 1x1-conv negative result live in
``pallas_kernels.py``; this kernel follows the same dispatch house style
(``fits_vmem``-like budget guard, loud fallbacks, lax path for training
parity tests).
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import register
from ..environment import precision_for
from .pallas_kernels import (available as _tpu_available,
                             partitioned as _partitioned)

_LANES = 128          # TPU lane count: running max/sum ride replicated lanes
_NEG = float(np.finfo(np.float32).min)


# --------------------------------------------------------------------------
# reference (quadratic) path — f32 softmax, shared by layers and fallbacks
# --------------------------------------------------------------------------

def reference_attention(q, k, v, bias=None, scale: Optional[float] = None):
    """Quadratic einsum attention with the kernel's numerics: scores in f32,
    softmax in f32, p@v accumulated in f32, output in the input dtype.

    q: [..., Tq, d]; k, v: [..., Tk, d]; bias broadcastable to
    [..., Tq, Tk] (additive, finite large-negative for masking)."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    s = jnp.einsum("...qd,...kd->...qk", q, k,
                   precision=precision_for(q, k),
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + jnp.maximum(bias.astype(jnp.float32), _NEG)
    p = jax.nn.softmax(s, axis=-1)
    y = jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v,
                   precision=precision_for(v, v),
                   preferred_element_type=jnp.float32)
    return y.astype(q.dtype)


# --------------------------------------------------------------------------
# kernel bodies
# --------------------------------------------------------------------------

def _lanes(x, n):
    """[rows, _LANES] lane-replicated stat -> [rows, n] broadcast."""
    if x.shape[1] == n:
        return x
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


class BlockMask(NamedTuple):
    """A causal mask over a (query block, key block) grid, static: key ``j``
    is open to query ``i`` where ``i - window < j <= i`` (``window`` None: no
    lower edge). What the blocked kernels ask of it is which blocks a block
    reaches, so that a pair the mask closes wholly is neither computed nor
    fetched, and whether the mask cuts through a pair, so that only those pay
    for the ``where``. The block arguments are Python ints (the tiling rule
    counts with them) or the kernels' and index maps' traced grid positions."""
    bq: int
    bk: int
    t: int
    window: Optional[int] = None

    @property
    def nq(self) -> int:
        return self.t // self.bq

    @property
    def nk(self) -> int:
        return self.t // self.bk

    def first_key(self, i):
        """The first key block query block ``i`` reaches."""
        if self.window is None:
            return 0 * i
        return _at_least_0(i * self.bq - self.window + 1) // self.bk

    def last_key(self, i):
        return (i * self.bq + self.bq - 1) // self.bk

    def first_query(self, j):
        """The first query block that reaches key block ``j``."""
        return j * self.bk // self.bq

    def last_query(self, j):
        if self.window is None:
            return 0 * j + self.nq - 1
        last = (j * self.bk + self.bk + self.window - 2) // self.bq
        return _at_most(last, self.nq - 1)

    @property
    def key_span(self) -> int:
        """The most key blocks any query block reaches: the key axis of a
        grid whose position ``j`` is key block ``first_key(i) + j``."""
        return max(self.last_key(i) - self.first_key(i) + 1
                   for i in range(self.nq))

    @property
    def query_span(self) -> int:
        return max(self.last_query(j) - self.first_query(j) + 1
                   for j in range(self.nk))

    def open_blocks(self) -> int:
        """Pairs of blocks with an open pair inside: the grid steps that
        compute."""
        return sum(self.last_key(i) - self.first_key(i) + 1
                   for i in range(self.nq))

    def cuts(self, i, j):
        """Whether a pair inside (query block ``i``, key block ``j``) is
        closed. The pair is taken to hold an open one."""
        cut = j * self.bk + self.bk - 1 > i * self.bq
        if self.window is not None:
            cut |= j * self.bk <= i * self.bq + self.bq - 1 - self.window
        return cut

    def open(self, i, j, transposed: bool = False):
        """The tile of (query block ``i``, key block ``j``), ``[bq, bk]`` or
        transposed ``[bk, bq]``: True where the pair is open."""
        shape = (self.bk, self.bq) if transposed else (self.bq, self.bk)
        q_axis = 1 if transposed else 0
        ahead = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis) \
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis) \
            + (i * self.bq - j * self.bk)         # query - key position
        ok = ahead >= 0
        if self.window is not None:
            ok &= ahead < self.window
        return ok


def _at_least_0(x):
    return max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)


def _at_most(x, cap: int):
    return min(x, cap) if isinstance(x, int) else jnp.minimum(x, cap)


def _masked_steps(mask: Optional[BlockMask], i, j, step):
    """Run ``step(cut)`` for the pair (query block ``i``, key block ``j``):
    always without a mask; under one only where the pair holds an open
    pair, with ``cut`` saying whether it needs the ``where``."""
    if mask is None:
        step(False)
        return
    run = (j >= mask.first_key(i)) & (j <= mask.last_key(i)) \
        & (i < mask.nq)
    cut = mask.cuts(i, j)
    pl.when(run & cut)(functools.partial(step, True))
    pl.when(run & jnp.logical_not(cut))(functools.partial(step, False))


def _as_row(col):
    """``[rows, _LANES]`` lane-replicated -> ``[1, rows]``."""
    return jnp.transpose(col)[:1]


def _as_column(row):
    """``[1, rows]`` -> ``[rows, _LANES]`` lane-replicated."""
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, row.shape[1])))


def _stacked(ref):
    """The query side's block of the ``G`` query heads of one KV head,
    ``[G, bq, w]``, as one ``[G * bq, w]`` tile."""
    x = ref[...]
    return x.reshape(x.shape[0] * x.shape[1], x.shape[2])


def _stacked_row(ref):
    """The ``G`` heads' ``[1, bq]`` statistics rows side by side, ``[1, G *
    bq]``: the transposed tile's columns in :func:`_stacked`'s order."""
    return jnp.concatenate([ref[g] for g in range(ref.shape[0])], axis=1)


def _stacked_column(ref):
    """The same rows as one lane-replicated ``[G * bq, _LANES]`` column."""
    return jnp.concatenate([_as_column(ref[g]) for g in range(ref.shape[0])],
                           axis=0)


def _open(mask: BlockMask, i, j, cut, sel_ref, transposed: bool = False):
    """One head's open pairs in the tile: the causal cut where ``cut``;
    under a mask that is data (``sel_ref``: its ``[bq, bk]`` tile, or the
    transposed mask's ``[bk, bq]``, nonzero where the pair is open) that
    tile, ANDed with the cut where there is one."""
    if sel_ref is None:
        return mask.open(i, j, transposed)
    open_ = sel_ref[0].astype(jnp.int32) != 0
    if cut:
        open_ &= mask.open(i, j, transposed)
    return open_


def _closed(s, open_, transposed: bool = False):
    """``s`` with the pairs ``open_`` leaves shut at ``_NEG``; ``open_`` is
    one head's and ``s`` may stack several along its query axis."""
    reps = s.shape[1 if transposed else 0] // open_.shape[1 if transposed
                                                          else 0]
    if reps > 1:
        open_ = jnp.tile(open_, (1, reps) if transposed else (reps, 1))
    return jnp.where(open_, s, _NEG)


def _scores(q_ref, k_ref, bias_ref, scale, stacked: bool = False):
    q = _stacked(q_ref) if stacked else q_ref[0]
    s = jax.lax.dot_general(
        q, k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)  # [1, bk] broadcasts rows
    return s


def _fwd_kernel(*refs, scale, nk, has_bias, mask: Optional[BlockMask] = None,
                stacked: bool = False):
    """Under a ``mask`` the key axis of the grid spans only the blocks a
    query block reaches (position ``j`` is key block ``first_key(i) + j``),
    and the statistics leave as one compact row, the logsumexp ``[1, bq]``:
    every row has a key open (a causal row its own), so its maximum is a
    score and cannot absorb ``log(l)`` as a mask bias can. A row may meet
    whole tiles closed before its first open key: their ``exp(0)`` terms
    are wiped by ``alpha = exp(_NEG - score) = 0`` once a score arrives.

    ``stacked``: a mask that is data too. Its ``[bq, bk]`` tile follows the
    values as 8-bit integers (nonzero: open), and the query side's blocks
    hold the ``G`` query heads of one KV head (``[G, bq, w]``, logsumexp
    ``[G, 1, bq]``), scored as one ``[G * bq, bk]`` tile under the one mask
    tile, so that the mask is fetched once a KV head."""
    bias_ref = sel_ref = None
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, o_ref, m_ref, l_ref,
         m_scr, l_scr, acc_scr) = refs
    elif stacked:
        (q_ref, k_ref, v_ref, sel_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    elif mask is not None:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    i, j = pl.program_id(1), pl.program_id(2)
    kb = j if mask is None else mask.first_key(i) + j
    d = acc_scr.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def step(cut):
        s = _scores(q_ref, k_ref, bias_ref, scale, stacked)  # [bq, bk] f32
        if cut or stacked:
            s = _closed(s, _open(mask, i, kb, cut, sel_ref))
        m_prev, l_prev = m_scr[...], l_scr[...]            # [bq, LANES]
        m_curr = jnp.max(s, axis=1, keepdims=True)         # [bq, 1]
        m_next = jnp.maximum(m_prev, m_curr)               # [bq, LANES]
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _lanes(m_next, s.shape[1]))        # [bq, bk]
        m_scr[...] = m_next
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * _lanes(alpha, d) + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)

    _masked_steps(mask, i, kb, step)

    @pl.when(j == nk - 1)
    def _finish():
        l_fin = l_scr[...]
        safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        if stacked:
            o = (acc_scr[...] / _lanes(safe, d)).astype(o_ref.dtype)
            o_ref[...] = o.reshape(o_ref.shape)
            lse = m_scr[...] + jnp.log(safe)
            bq = lse_ref.shape[-1]
            for g in range(lse_ref.shape[0]):
                lse_ref[g] = _as_row(lse[g * bq:(g + 1) * bq])
            return
        o_ref[0] = (acc_scr[...] / _lanes(safe, d)).astype(o_ref.dtype)
        if mask is not None:
            lse_ref[0] = _as_row(m_scr[...] + jnp.log(safe))
            return
        # the softmax stats are saved as SEPARATE max + sum (the logsumexp
        # in two pieces): m + log(l) would absorb log(l) entirely when m is
        # a finfo.min mask bias (ulp(3e38) >> log l), and the backward's
        # recomputed p = exp(s - lse) would come out 1 instead of 1/Tk on
        # fully-masked rows (found by the masked-row gradient parity test)
        m_ref[0] = m_scr[...]
        l_ref[0] = safe


def _bwd_dq_kernel(*refs, scale, nk, has_bias,
                   mask: Optional[BlockMask] = None, stacked: bool = False):
    """Under a ``mask``: the grid of :func:`_fwd_kernel`, and the compact
    ``[1, bq]`` logsumexp and ``di`` rows turned into columns once a query
    block; ``stacked`` as there (the mask's tile follows the values)."""
    bias_ref = sel_ref = None
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref, di_ref, do_ref,
         dq_ref, dq_scr) = refs
    elif stacked:
        (q_ref, k_ref, v_ref, sel_ref, lse_ref, di_ref, do_ref,
         dq_ref, dq_scr, lse_scr, di_scr) = refs
    elif mask is not None:
        (q_ref, k_ref, v_ref, lse_ref, di_ref, do_ref,
         dq_ref, dq_scr, lse_scr, di_scr) = refs
    else:
        (q_ref, k_ref, v_ref, m_ref, l_ref, di_ref, do_ref,
         dq_ref, dq_scr) = refs
    i, j = pl.program_id(1), pl.program_id(2)
    kb = j if mask is None else mask.first_key(i) + j

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)
        if stacked:
            lse_scr[...] = _stacked_column(lse_ref)
            di_scr[...] = _stacked_column(di_ref)
        elif mask is not None:
            lse_scr[...] = _as_column(lse_ref[0])
            di_scr[...] = _as_column(di_ref[0])

    def step(cut):
        s = _scores(q_ref, k_ref, bias_ref, scale, stacked)
        bk = s.shape[1]
        if mask is None:
            p = jnp.exp(s - _lanes(m_ref[0], bk)) * _lanes(1.0 / l_ref[0], bk)
            di = di_ref[0]
        else:
            if cut or stacked:
                s = _closed(s, _open(mask, i, kb, cut, sel_ref))
            p = jnp.exp(s - _lanes(lse_scr[...], bk))
            di = di_scr[...]
        dp = jax.lax.dot_general(                           # do @ v^T
            _stacked(do_ref) if stacked else do_ref[0], v_ref[0],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(di, bk)) * scale              # [bq, bk] f32
        dq_scr[...] += jax.lax.dot(ds.astype(k_ref.dtype), k_ref[0],
                                   preferred_element_type=jnp.float32)

    _masked_steps(mask, i, kb, step)

    @pl.when(j == nk - 1)
    def _finish():
        if stacked:
            dq_ref[...] = dq_scr[...].astype(dq_ref.dtype).reshape(
                dq_ref.shape)
            return
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, nq, has_bias):
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref, di_ref, do_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, m_ref, l_ref, di_ref, do_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        bias_ref = None
    jq = pl.program_id(2)

    @pl.when(jq == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    s = _scores(q_ref, k_ref, bias_ref, scale)              # [bq, bk]
    bk = s.shape[1]
    p = jnp.exp(s - _lanes(m_ref[0], bk)) * _lanes(1.0 / l_ref[0], bk)
    do = do_ref[0]
    dv_scr[...] += jax.lax.dot_general(                     # p^T @ do
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(                               # do @ v^T
        do, v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - _lanes(di_ref[0], bk)) * scale
    dk_scr[...] += jax.lax.dot_general(                     # ds^T @ q
        ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jq == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dkv_masked_kernel(*refs, scale, mask: BlockMask, group: int,
                           stacked: bool = False):
    """dk and dv of one key block under a ``mask``, summed over the query
    blocks that reach it and over the ``group`` query heads that read its KV
    head: grid position ``t`` of the inner axis is head ``t // span`` and
    query block ``first_query(j) + t % span``. The tile is held TRANSPOSED,
    ``[bk, bq]``, as in :func:`_bwd_row_kernel`: the compact ``[1, bq]``
    logsumexp and ``di`` rows broadcast down the keys as they are, and dv =
    p^T do and dk = ds^T q are plain products. ``stacked`` as in
    :func:`_fwd_kernel`, with the TRANSPOSED mask's ``[bk, bq]`` tile after
    the values and the KV head's query heads in the tile (``group`` 1 in the
    grid): ``[bk, G * bq]``."""
    sel_ref = None
    if stacked:
        (q_ref, k_ref, v_ref, sel_ref, lse_ref, di_ref, do_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, lse_ref, di_ref, do_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    j, t = pl.program_id(1), pl.program_id(2)
    span = mask.query_span
    i = mask.first_query(j) + jax.lax.rem(t, span)

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def step(cut):
        if stacked:
            q, do = _stacked(q_ref), _stacked(do_ref)
        else:
            q, do = q_ref[0], do_ref[0]
        s = jax.lax.dot_general(                            # k @ q^T
            k_ref[0], q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [bk, bq] f32
        if cut or stacked:
            s = _closed(s, _open(mask, i, j, cut, sel_ref, transposed=True),
                        transposed=True)
        p = jnp.exp(s - (_stacked_row(lse_ref) if stacked else lse_ref[0]))
        dv_scr[...] += jax.lax.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(                           # v @ do^T
            v_ref[0], do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - (_stacked_row(di_ref) if stacked
                        else di_ref[0])) * scale
        dk_scr[...] += jax.lax.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    _masked_steps(mask, i, j, step)

    @pl.when(t == group * span - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _fwd_row_kernel(*refs, scale, has_bias):
    """Forward where one tile holds a query block's whole key row (nk = 1):
    a plain softmax, so no running max/sum, no ``alpha`` rescale, no
    init/finish branches and no statistics to save (the backward has the
    whole row too and works the softmax out again). Blocks carry ``hb``
    heads of one batch row; the loop over them is unrolled."""
    refs = list(refs)
    bias = refs.pop(3)[0].astype(jnp.float32) if has_bias else None
    q_ref, k_ref, v_ref, o_ref = refs
    for h in range(q_ref.shape[0]):
        s = jax.lax.dot_general(
            q_ref[h], k_ref[h], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [bq, Tk] f32
        if bias is not None:
            s = s + bias                                    # [1, Tk] rows
        e = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
        l = jnp.sum(e, axis=1, keepdims=True)               # >= 1: the max
        acc = jax.lax.dot(e.astype(v_ref.dtype), v_ref[h],
                          preferred_element_type=jnp.float32)
        o_ref[h] = (acc / l).astype(o_ref.dtype)


def _bwd_row_kernel(*refs, scale, nq, has_bias):
    """dk, dv and dq of a whole-key-row tile in ONE kernel: p and ds are
    formed once. The tile is held TRANSPOSED, ``[Tk, bq]``, so the softmax
    runs down the sublanes with per-query ``[1, bq]`` rows (nothing
    lane-replicated), dv = p^T do and dk = ds^T q are plain products and only
    dq contracts the tile's first axis. ``di = sum_k p dp`` (equal to
    ``sum(o * do)``, in f32 from the tile itself), so neither ``o`` nor any
    saved statistic is read. With nq > 1 the q-blocks are the inner,
    sequential grid axis and dk/dv accumulate in f32 scratch."""
    refs = list(refs)
    bias = refs.pop(3)[0].astype(jnp.float32) if has_bias else None
    q_ref, k_ref, v_ref, do_ref, dk_ref, dv_ref, dq_ref, *scratch = refs
    if nq > 1:
        dk_scr, dv_scr = scratch
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
            dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    for h in range(q_ref.shape[0]):
        q, k, do = q_ref[h], k_ref[h], do_ref[h]
        s = jax.lax.dot_general(                            # k @ q^T
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [Tk, bq] f32
        if bias is not None:
            s = s + bias                                    # [Tk, 1] column
        e = jnp.exp(s - jnp.max(s, axis=0, keepdims=True))
        p = e * (1.0 / jnp.sum(e, axis=0, keepdims=True))
        dp = jax.lax.dot_general(                           # v @ do^T
            v_ref[h], do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        di = jnp.sum(p * dp, axis=0, keepdims=True)         # [1, bq]
        ds = p * (dp - di) * scale
        dv = jax.lax.dot(p.astype(do.dtype), do,
                         preferred_element_type=jnp.float32)
        dk = jax.lax.dot(ds.astype(q.dtype), q,
                         preferred_element_type=jnp.float32)
        dq_ref[h] = jax.lax.dot_general(                    # ds^T @ k
            ds.astype(k.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dq_ref.dtype)
        if nq > 1:
            dk_scr[h] += dk
            dv_scr[h] += dv
        else:
            dk_ref[h] = dk.astype(dk_ref.dtype)
            dv_ref[h] = dv.astype(dv_ref.dtype)

    if nq > 1:
        @pl.when(i == nq - 1)
        def _finish():
            dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _mq_decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr, *, scale, nk, bk, heads):
    """Multi-query decode forward (speculative verify, ISSUE 12): the
    whole Tq=k query window rides one grid row, streaming the cache in
    ``bk`` tiles. The mask is computed INSIDE the kernel from the per-row
    valid length: query i (global position ``l + i``) may attend cache
    columns ``< l + 1 + i`` — a per-(query, key) causal window that is
    not key-reducible, so it cannot ride the fwd kernel's key bias. The
    lengths arrive by scalar prefetch ([B] int32 in SMEM)."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale          # [bq, bk] f32
    ln = len_ref[pl.program_id(0) // heads]                  # int32 scalar
    col = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    s = jnp.where(col < ln + 1 + row, s, _NEG)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_curr = jnp.max(s, axis=1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_curr)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - _lanes(m_next, s.shape[1]))
    m_scr[...] = m_next
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    d = acc_scr.shape[1]
    acc_scr[...] = acc_scr[...] * _lanes(alpha, d) + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        l_fin = l_scr[...]
        safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0] = (acc_scr[...] / _lanes(safe, d)).astype(o_ref.dtype)


# lazily bound so importing this module never requires pallas to load
pl = None


def _load_pallas():
    global pl
    if pl is None:
        from jax.experimental import pallas as _pl
        pl = _pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


def _compiler_params(pltpu, semantics=("parallel", "parallel", "arbitrary"),
                     vmem_bytes: int = 0):
    """``vmem_bytes``: what :func:`vmem_bytes_attention` counts for the
    tiling. One that passes three quarters of Mosaic's default scoped VMEM
    asks the compiler for its bytes and half again, rather than the tiling
    staying under a guess."""
    limit = None
    if vmem_bytes > _SCOPED_VMEM * 3 // 4:
        limit = vmem_bytes * 3 // 2
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


# --------------------------------------------------------------------------
# pallas_call wrappers (grid = (B*H, q-blocks, kv-blocks))
# --------------------------------------------------------------------------

def _fwd_impl(q3, k3, v3, kb, scale, heads, bq, bk, interpret):
    pl, pltpu = _load_pallas()
    G, Tq, d = q3.shape
    Tk = k3.shape[1]
    nq, nk = Tq // bq, Tk // bk
    has_bias = kb is not None
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
    ]
    args = [q3, k3, v3]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b // heads, 0, j)))
        args.append(kb)
    kernel = functools.partial(_fwd_kernel, scale=scale, nk=nk,
                               has_bias=has_bias)
    row = pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0))
    o, m, l = pl.pallas_call(
        kernel,
        grid=(G, nq, nk),
        in_specs=in_specs,
        out_shape=(jax.ShapeDtypeStruct((G, Tq, d), q3.dtype),
                   jax.ShapeDtypeStruct((G, Tq, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((G, Tq, _LANES), jnp.float32)),
        out_specs=(pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
                   row, row),
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params(pltpu, vmem_bytes=vmem_bytes_attention(
            bq, bk, d, q3.dtype.itemsize)),
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    return o, m, l


def _bwd_impl(q3, k3, v3, kb, m, l, di, do, scale, heads, bq, bk, interpret):
    pl, pltpu = _load_pallas()
    G, Tq, d = q3.shape
    Tk = k3.shape[1]
    nq, nk = Tq // bq, Tk // bk
    has_bias = kb is not None

    qkv_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),   # q by i
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),   # k by j
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),   # v by j
    ]
    bias_spec = [pl.BlockSpec((1, 1, bk),
                              lambda b, i, j: (b // heads, 0, j))] \
        if has_bias else []
    row_specs = [
        pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),  # m
        pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),  # l
        pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),  # di
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),       # do
    ]
    args = [q3, k3, v3] + ([kb] if has_bias else []) + [m, l, di, do]
    params = _compiler_params(pltpu, vmem_bytes=vmem_bytes_attention(
        bq, bk, d, q3.dtype.itemsize))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, nk=nk,
                          has_bias=has_bias),
        grid=(G, nq, nk),
        in_specs=qkv_specs + bias_spec + row_specs,
        out_shape=jax.ShapeDtypeStruct((G, Tq, d), q3.dtype),
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dq",
    )(*args)

    # dk/dv grid: kv-blocks outer, q-blocks inner (the reduction axis)
    dkv_qkv_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, j, 0)),   # q by inner j
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0)),   # k by outer i
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0)),   # v by outer i
    ]
    dkv_bias_spec = [pl.BlockSpec((1, 1, bk),
                                  lambda b, i, j: (b // heads, 0, i))] \
        if has_bias else []
    dkv_row_specs = [
        pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, j, 0)),  # m
        pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, j, 0)),  # l
        pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, j, 0)),  # di
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, j, 0)),       # do
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, nq=nq,
                          has_bias=has_bias),
        grid=(G, nk, nq),
        in_specs=dkv_qkv_specs + dkv_bias_spec + dkv_row_specs,
        out_shape=(jax.ShapeDtypeStruct((G, Tk, d), k3.dtype),
                   jax.ShapeDtypeStruct((G, Tk, d), v3.dtype)),
        out_specs=(pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0))),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*args)
    return dq, dk, dv


def _fwd_row_impl(q3, k3, v3, kb, scale, heads, bq, interpret):
    """grid = (B*H / hb, q-blocks): each step holds ``hb`` heads of one batch
    row (:func:`heads_per_step`), a query block of them and their whole key
    rows."""
    pl, pltpu = _load_pallas()
    G, Tq, d = q3.shape
    Tk = k3.shape[1]
    hb = heads_per_step(heads, bq, Tk, d, q3.dtype.itemsize)
    has_bias = kb is not None
    in_specs = [
        pl.BlockSpec((hb, bq, d), lambda g, i: (g, i, 0)),
        pl.BlockSpec((hb, Tk, d), lambda g, i: (g, 0, 0)),
        pl.BlockSpec((hb, Tk, d), lambda g, i: (g, 0, 0)),
    ]
    args = [q3, k3, v3]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, Tk), lambda g, i: (g * hb // heads, 0, 0)))
        args.append(kb)
    return pl.pallas_call(
        functools.partial(_fwd_row_kernel, scale=scale, has_bias=has_bias),
        grid=(G // hb, Tq // bq),
        in_specs=in_specs,
        out_shape=jax.ShapeDtypeStruct((G, Tq, d), q3.dtype),
        out_specs=pl.BlockSpec((hb, bq, d), lambda g, i: (g, i, 0)),
        compiler_params=_compiler_params(
            pltpu, ("parallel", "parallel"),
            vmem_bytes_attention(bq, Tk, d, q3.dtype.itemsize, hb)),
        interpret=interpret,
        name="flash_fwd",
    )(*args)


def _bwd_row_impl(q3, k3, v3, kb, do, scale, heads, bq, interpret):
    """The fused whole-row backward; ``flash_bwd_dkv`` with dk as its first
    result, which is how the benchmark's readers know the dk/dv kernel."""
    pl, pltpu = _load_pallas()
    G, Tq, d = q3.shape
    Tk = k3.shape[1]
    nq = Tq // bq
    hb = heads_per_step(heads, bq, Tk, d, q3.dtype.itemsize)
    has_bias = kb is not None
    by_q = pl.BlockSpec((hb, bq, d), lambda g, i: (g, i, 0))
    by_k = pl.BlockSpec((hb, Tk, d), lambda g, i: (g, 0, 0))
    in_specs, args = [by_q, by_k, by_k], [q3, k3, v3]
    if has_bias:
        # the tile is [Tk, bq]: the key bias rides as a column
        in_specs.append(
            pl.BlockSpec((1, Tk, 1), lambda g, i: (g * hb // heads, 0, 0)))
        args.append(kb.reshape(kb.shape[0], Tk, 1))
    dk, dv, dq = pl.pallas_call(
        functools.partial(_bwd_row_kernel, scale=scale, nq=nq,
                          has_bias=has_bias),
        grid=(G // hb, nq),
        in_specs=in_specs + [by_q],
        out_shape=(jax.ShapeDtypeStruct((G, Tk, d), k3.dtype),
                   jax.ShapeDtypeStruct((G, Tk, d), v3.dtype),
                   jax.ShapeDtypeStruct((G, Tq, d), q3.dtype)),
        out_specs=(by_k, by_k, by_q),
        scratch_shapes=[pltpu.VMEM((hb, Tk, d), jnp.float32)] * 2
        if nq > 1 else [],
        compiler_params=_compiler_params(
            pltpu, ("parallel", "arbitrary"),
            vmem_bytes_attention(bq, Tk, d, q3.dtype.itemsize, hb)),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*args, do)
    return dq, dk, dv


def _mq_impl(q3, k3, v3, lens, scale, heads, bk, interpret):
    """pallas_call wrapper for the Tq=k multi-query decode kernel: the
    whole query window is one block (bq = Tq), the cache streams in
    ``bk`` tiles, ``lens`` is the [B] int32 valid-length array, scalar-
    prefetched (forward only — verify never trains)."""
    pl, pltpu = _load_pallas()
    G, Tq, d = q3.shape
    Tk = k3.shape[1]
    nk = Tk // bk
    kernel = functools.partial(_mq_decode_kernel, scale=scale, nk=nk, bk=bk,
                               heads=heads)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G, 1, nk),
            in_specs=[
                pl.BlockSpec((1, Tq, d), lambda b, i, j, lens: (b, 0, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i, j, lens: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i, j, lens: (b, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, Tq, d),
                                   lambda b, i, j, lens: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((Tq, _LANES), jnp.float32),
                            pltpu.VMEM((Tq, _LANES), jnp.float32),
                            pltpu.VMEM((Tq, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((G, Tq, d), q3.dtype),
        compiler_params=_compiler_params(pltpu),
        interpret=interpret,
        name="flash_decode_mq",
    )(lens, q3, k3, v3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q3, k3, v3, kb, scale, heads, bq, bk, interpret):
    return _flash_fwd(q3, k3, v3, kb, scale, heads, bq, bk, interpret)[0]


def _flash_fwd(q3, k3, v3, kb, scale, heads, bq, bk, interpret):
    """``bk`` the whole key row takes the whole-row kernels, which save
    nothing but their operands; a blocked grid saves o and the two pieces of
    the logsumexp for its two backward kernels."""
    if bk == k3.shape[1]:
        o = _fwd_row_impl(q3, k3, v3, kb, scale, heads, bq, interpret)
        return o, (q3, k3, v3, kb)
    o, m, l = _fwd_impl(q3, k3, v3, kb, scale, heads, bq, bk, interpret)
    return o, (q3, k3, v3, kb, o, m, l)


def _flash_bwd(scale, heads, bq, bk, interpret, res, do):
    q3, k3, v3, kb = res[:4]
    if bk == k3.shape[1]:
        dq, dk, dv = _bwd_row_impl(q3, k3, v3, kb, do, scale, heads, bq,
                                   interpret)
    else:
        o, m, l = res[4:]
        di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                     axis=-1, keepdims=True)
        di = jnp.broadcast_to(di, m.shape)  # lane-replicated like m/l
        dq, dk, dv = _bwd_impl(q3, k3, v3, kb, m, l, di, do,
                               scale, heads, bq, bk, interpret)
    # bias is mask-derived here: zero cotangent (recorded divergence)
    dkb = None if kb is None else jnp.zeros_like(kb)
    return dq, dk, dv, dkb


_flash.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------------------------------------
# public fused op
# --------------------------------------------------------------------------

def divisor_blocks(t: int, cap: int):
    """Multiple-of-8 blocks <= ``cap`` that divide ``t``, largest first."""
    b = min(int(cap), int(t))
    b -= b % 8
    while b >= 8:
        if t % b == 0:
            yield b
        b -= 8


def pick_block(t: int, target: int = 128) -> Optional[int]:
    """Largest block <= target that divides ``t`` and is a multiple of 8
    (layout-friendly sublanes); None when nothing tiles.

    r12: any multiple-of-8 divisor qualifies, not only power-of-two tiles —
    odd sequence lengths like 24, 120 or 384 now tile (fewer dispatcher
    ``fallback_shape`` exits) instead of demanding a power-of-two factor."""
    return next(divisor_blocks(t, target), None)


def kv_block_ok(bk: int, tk: int, has_bias: bool) -> bool:
    """The key bias rides as ``[B, 1, Tk]`` in ``(1, 1, bk)`` blocks, and
    the TPU lowering wants a block's lane dim a multiple of 128 or the
    whole row."""
    return not has_bias or bk % _LANES == 0 or bk == tk


def pick_kv_block(tk: int, target: int = 128,
                  has_bias: bool = False) -> Optional[int]:
    """:func:`pick_block` for the kv axis, held to :func:`kv_block_ok`."""
    bk = pick_block(tk, target)
    if bk is None or kv_block_ok(bk, tk, has_bias):
        return bk
    b = int(target) - int(target) % _LANES
    while b >= _LANES:
        if tk % b == 0:
            return b
        b -= _LANES
    return None


#: the largest query block: the default tiling and the autotuner's candidates
#: stop here on the query axis (1024 rows read 3% faster than 512 at
#: [192, 1024, 64] and hold twice the VMEM; PERF.md section 6, PR 32). The
#: key axis has no cap but the VMEM guard.
MAX_BLOCK = 512
_SCOPED_VMEM = 16 * 2 ** 20      # Mosaic's default scoped VMEM
#: what a tiling may count in :func:`vmem_bytes_attention`. The compiler is
#: asked for half again (``_compiler_params``): 48 MiB, under the 64 MiB of
#: the smallest current core (v5e and v6e have 128 MiB).
_VMEM_TILE_BUDGET = 32 * 2 ** 20


def heads_per_step(heads: int, bq: int, bk: int, d: int,
                   itemsize: int = 4) -> int:
    """Heads a whole-row grid step carries: the most (dividing ``heads``, so
    a step stays inside one batch row and one key-bias row) whose score
    tiles together stay within one ``MAX_BLOCK`` square and whose blocks
    fit. A short row is a small tile, and a grid step costs about 0.35 us
    whatever is in it: at [1536, 128, 64] twelve heads a step take the
    forward from 0.65 to 0.19 ms (PERF.md section 6, PR 32)."""
    room = MAX_BLOCK * MAX_BLOCK // (bq * bk)
    return max([1] + [h for h in range(2, min(heads, room) + 1)
                      if heads % h == 0 and vmem_bytes_attention(
                          bq, bk, d, itemsize, h) <= _VMEM_TILE_BUDGET])


def vmem_bytes_attention(bq: int, bk: int, d: int, itemsize: int = 4,
                         hb: int = 1, mask_rows: int = 0) -> int:
    """VMEM a (bq, bk) tiling holds in the worst of its kernels, the
    backward: the blocks the pipeline fetches and writes back (q, do, k, v
    in; dq, dk, dv out; the m/l/di rows of a blocked grid or the key bias
    as a column of a whole-row tile), which are double-buffered; and, once,
    the f32 dk/dv scratch and two f32 score-sized temporaries. Mosaic
    streams the elementwise chains between the products and keeps about one
    and a half such tiles (the compiler's own count for a 2048 x 2048 bf16
    whole-row backward is 31.25 MiB, this one's 42.5). ``mask_rows``: the
    rows of a mask that is data, fetched beside the keys as an 8-bit
    ``[mask_rows, bk]`` tile (``bq`` then counts the query heads it
    serves)."""
    fetched = (2 * (bq + bk) + bq + 2 * bk) * hb * d * itemsize \
        + mask_rows * bk
    rows = max(3 * bq, bk) * _LANES * 4
    scratch = 2 * hb * bk * d * 4
    tiles = 2 * hb * bq * bk * 4
    return 2 * (fetched + rows) + scratch + tiles


def fits_vmem_attention(bq: int, bk: int, d: int, itemsize: int = 4,
                        mask_rows: int = 0) -> bool:
    """The one guard the dispatcher, the default tiling, the autotuner's
    candidates and the kernels' wrappers share."""
    return vmem_bytes_attention(bq, bk, d, itemsize,
                                mask_rows=mask_rows) <= _VMEM_TILE_BUDGET


def _tilings(tq: int, tk: int, has_bias: bool):
    """The (block_q, block_k) pairs that tile, in the default's order of
    preference: the largest query block up to ``MAX_BLOCK`` first, and under
    it the most keys."""
    for bq in divisor_blocks(tq, MAX_BLOCK):
        for bk in divisor_blocks(tk, tk):
            if kv_block_ok(bk, tk, has_bias):
                yield bq, bk


def default_blocks(tq: int, tk: int, d: int, itemsize: int = 4,
                   has_bias: bool = False):
    """(block_q, block_k) a shape gets when nobody tuned it, which is what
    every traced program gets: the largest tile that fits. The query block
    comes first, up to ``MAX_BLOCK`` rows (a backward's dk/dv pass over
    their f32 accumulators once a query block, so a short one costs: 128
    rows under 4,096 keys read 1.7x slower than 512), then the most keys
    that fit beside it. Where that is the whole key row (4,096 keys of
    d = 64 in bf16 under 512 queries) the whole-row kernels run: no
    online-softmax carry, one fused backward, nothing saved but the
    operands. A longer row keeps a blocked grid, and 128 is what the rule
    yields only where nothing larger divides the length. None when nothing
    tiles or fits."""
    return next((t for t in _tilings(tq, tk, has_bias)
                 if fits_vmem_attention(*t, d, itemsize)), None)


def tiling_kind(bk: int, tk: int) -> str:
    """``whole_row`` (nk = 1: the whole-row kernels) or ``blocked``."""
    return "whole_row" if bk == tk else "blocked"


def _key_bias(bias, batch, tk):
    """Reduce an additive bias broadcastable to [B,H,Tq,Tk] down to the
    per-(batch, key) form [B, 1, Tk] the kernel streams, or None if the
    bias genuinely varies over heads/queries."""
    if bias is None:
        return None
    if bias.ndim != 4 or bias.shape[1] != 1 or bias.shape[2] != 1:
        return None
    if bias.shape[0] not in (1, batch) or bias.shape[3] != tk:
        return None
    kb = jnp.broadcast_to(bias[:, 0, :, :], (batch, 1, tk))
    return jnp.maximum(kb.astype(jnp.float32), _NEG)


def flash_attention(q, k, v, bias=None, scale: Optional[float] = None, *,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False):
    """Fused flash attention: softmax((q.k^T)*scale + bias) @ v.

    q: [B, H, Tq, d]; k, v: [B, H, Tk, d]; bias broadcastable to
    [B, H, Tq, Tk] with singleton head/query dims (key-mask form — a
    full per-query bias falls outside this kernel; use the dispatcher,
    which falls back). Raises ValueError on non-tiling shapes — callers
    go through :func:`attention` for guarded dispatch.

    ``block_q``/``block_k``: explicit TARGET tile sizes (the largest
    divisor block <= target is used, the pre-r12 contract; 128 for the one
    left out). The default ``None`` takes :func:`default_blocks`, the
    largest tile that fits: up to 512 query rows under their whole key row
    (4,096 keys of d = 64 in bf16), a blocked grid beyond. A traced
    program (every ``fit``) always gets it, because a
    sweep cannot run mid-trace; a block-shape cache warmed beforehand
    (``ops/autotune.py``) overrides it for its (Tq, Tk, d, dtype, bias) key.
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(f"flash_attention wants [B,H,T,d]; got {q.shape}")
    B, H, Tq, d = q.shape
    Tk = k.shape[2]
    if k.shape != (B, H, Tk, d) or v.shape != (B, H, Tk, d):
        raise ValueError(f"q/k/v shapes disagree: {q.shape} {k.shape} "
                         f"{v.shape}")
    itemsize = np.dtype(q.dtype).itemsize
    if block_q is None and block_k is None:
        from . import autotune as _autotune
        tuned = _autotune.get_blocks(
            Tq, Tk, d, q.dtype, bias is not None,
            concrete=not isinstance(q, jax.core.Tracer))
        bq, bk = tuned if tuned is not None else (None, None)
        # belt over the autotuner's own validation: blocks that do not
        # tile would silently truncate the grid (Tq // bq); a poisoned
        # entry falls back to the default tiling, never garbage
        if bq is not None and (Tq % bq or Tk % bk or not kv_block_ok(
                bk, Tk, bias is not None)):
            bq, bk = default_blocks(Tq, Tk, d, itemsize,
                                    bias is not None) or (None, None)
    else:
        bq = pick_block(Tq, block_q or 128)
        bk = pick_kv_block(Tk, block_k or 128, bias is not None)
    if bq is None or bk is None:
        raise ValueError(f"sequence lengths ({Tq}, {Tk}) do not tile into "
                         f"({block_q or 128}, {block_k or 128}) blocks")
    if not fits_vmem_attention(bq, bk, d, itemsize):
        raise ValueError(f"attention tiles exceed the VMEM budget "
                         f"(bq={bq}, bk={bk}, d={d})")
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    kb = _key_bias(bias, B, Tk)
    if bias is not None and kb is None:
        raise ValueError(f"bias shape {bias.shape} is not key-reducible "
                         "([B,1,1,Tk]); use attention() for fallback")
    _TILING.inc(kind=tiling_kind(bk, Tk))
    o = _flash(q.reshape(B * H, Tq, d), k.reshape(B * H, Tk, d),
               v.reshape(B * H, Tk, d), kb, float(scale), H, bq, bk,
               bool(interpret))
    return o.reshape(B, H, Tq, d)


# --------------------------------------------------------------------------
# autoregressive decode: one new-token query over a bucketed KV cache
# --------------------------------------------------------------------------

def length_bias(lengths, cache_len: int):
    """Per-row valid-length mask ``[B, C]`` f32, zero where ``position <
    length`` and finfo.min elsewhere — with a unit middle axis, the key
    bias the forward kernel streams, so ragged cache occupancy stays exact
    without materializing a [B,H,1,C] mask."""
    lengths = jnp.asarray(lengths)
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, cache_len), 1)
    return jnp.where(pos < lengths[:, None].astype(jnp.int32),
                     jnp.float32(0.0), jnp.float32(_NEG))


def reference_decode_attention(q, k, v, lengths, scale=None):
    """Quadratic reference for single-step decode: ``q`` [B, H, 1, d]
    attends over the cache [B, H, C, d], positions >= ``lengths[b]``
    masked out. Shares :func:`reference_attention`'s f32 numerics."""
    C = k.shape[2]
    bias = length_bias(lengths, C)[:, None, None, :]
    return reference_attention(q, k, v, bias=bias, scale=scale)


def decode_attention(q, k, v, lengths, scale=None, *,
                     block_k: Optional[int] = None,
                     interpret: bool = False, page: int = 0):
    """Fused single-query decode: the flash forward kernel at ``bq=1``
    (forward only — decode is inference; no VJP needed) streaming the
    cache in ``block_k`` tiles with the per-row length mask as the key
    bias. ``q`` [B, H, 1, d]; ``k``/``v`` [B, H, C, d] (the HBM cache at
    its power-of-two bucket length); ``lengths`` [B] — the number of
    valid cache entries per row, the just-appended token included.

    ``block_k=None`` consults the autotuner under its ``decode=True``
    cache key (``ops/autotune.py``); explicit ints keep the target-block
    semantics. Raises ValueError on non-tiling shapes — serving goes
    through :func:`decode_dispatch` for guarded dispatch."""
    if q.ndim != 4 or q.shape[2] != 1:
        raise ValueError(f"decode_attention wants q [B,H,1,d]; got {q.shape}")
    B, H, _, d = q.shape
    C = k.shape[2]
    if k.shape != (B, H, C, d) or v.shape != (B, H, C, d):
        raise ValueError(f"q/cache shapes disagree: {q.shape} {k.shape} "
                         f"{v.shape}")
    if block_k is None:
        from . import autotune as _autotune
        tuned = _autotune.get_blocks(
            1, C, d, q.dtype, True, decode=True, page=page,
            concrete=not isinstance(q, jax.core.Tracer))
        bk = tuned[1] if tuned is not None else None
        if bk is not None and (C % bk or not kv_block_ok(bk, C, True)):
            # belt: a poisoned entry must not truncate
            bk = pick_kv_block(C, has_bias=True)
    else:
        bk = pick_kv_block(C, block_k, True)
    if bk is None:
        raise ValueError(f"cache length {C} does not tile into decode "
                         "blocks; bucket the cache to a power of two")
    if not fits_vmem_attention(1, bk, d, np.dtype(q.dtype).itemsize):
        raise ValueError(f"decode tiles exceed the VMEM budget "
                         f"(bk={bk}, d={d})")
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    kb = length_bias(lengths, C)[:, None, :]
    o, _, _ = _fwd_impl(q.reshape(B * H, 1, d), k.reshape(B * H, C, d),
                        v.reshape(B * H, C, d), kb, float(scale), H, 1, bk,
                        bool(interpret))
    return o.reshape(B, H, 1, d)


def cache_insert(cache, new, lengths, write=None):
    """Append one token window's K or V rows into a bucketed cache:
    ``cache`` [B, H, C, d], ``new`` [B, H, k, d] (k = 1 for plain decode,
    k > 1 for a speculative verify window), written at positions
    ``lengths[b] .. lengths[b]+k-1`` per row via a vmapped
    ``dynamic_update_slice`` — O(B*H*k*d) bytes touched instead of a
    one-hot select over the whole cache, and with donated buffers (the
    serving decode executables) XLA updates the HBM cache in place.

    ``write`` [B] (optional 0/1): rows with ``write == 0`` keep their
    cache bit-identical — the window's values at the target positions are
    replaced by a gather of what is already there, so a full-cache
    select is never needed (the continuous batcher's inactive slots).
    Out-of-range ``lengths`` clamp (XLA slice semantics) and the gathered
    old values make the clamped write a no-op, so a freed slot's stale
    length can never corrupt a neighbour."""
    lengths = jnp.asarray(lengths).astype(jnp.int32)
    new = new.astype(cache.dtype)
    if write is not None:
        kw = new.shape[2]
        old = jax.vmap(
            lambda c, l: jax.lax.dynamic_slice(
                c, (0, l, 0), (c.shape[0], kw, c.shape[2])))(cache, lengths)
        keep = jnp.asarray(write).astype(bool)[:, None, None, None]
        new = jnp.where(keep, new, old)
    return jax.vmap(
        lambda c, n, l: jax.lax.dynamic_update_slice(c, n, (0, l, 0)))(
        cache, new, lengths)


# --------------------------------------------------------------------------
# paged KV cache: page-table gather/scatter over a token-row pool (ISSUE 12)
# --------------------------------------------------------------------------
# The pool stores one layer's K or V cache as [n_pages * page_size, H, d]
# token rows; a host-side page table [S, MP] maps each slot's logical page
# j to a physical page id. Shapes stay static (the serving zero-compile
# contract): the gathered per-slot cache is always [S, H, MP*page_size, d]
# and the usual length bias masks the unoccupied tail, so ragged occupancy
# and partially-filled pages stay exact. Page id 0 is reserved as the
# zero page: unallocated table entries point there, and write-gated rows
# scatter back the value they gathered, so a freed/inactive slot can never
# corrupt a page another slot (or the prefix registry) still references.

def paged_positions(page_table, positions, page_size: int):
    """Physical token rows for logical positions: ``page_table`` [S, MP]
    int32, ``positions`` [S, k] -> [S, k] int32. Out-of-table positions
    clamp to the last page entry (XLA gather semantics) — callers gate
    those writes, mirroring ``cache_insert``'s stale-length contract."""
    P = int(page_size)
    positions = jnp.asarray(positions).astype(jnp.int32)
    pi = jnp.clip(positions // P, 0, page_table.shape[1] - 1)
    page = jnp.take_along_axis(page_table, pi, axis=1)
    return page * P + positions % P


def paged_gather(pool, page_table, page_size: int):
    """Materialize per-slot caches from the pool: ``pool``
    [NP, H, d] token rows, ``page_table`` [S, MP] -> [S, H, MP*P, d] —
    the gather-indices form the ISSUE 12 tentpole threads through
    ``decode_attention``/``cached_sdpa``. The gather is a temp (the
    attention kernel reads every valid row anyway); only the POOL is
    persistent HBM, which is what paging shrinks."""
    P = int(page_size)
    S, MP = page_table.shape
    idx = (page_table[:, :, None].astype(jnp.int32) * P
           + jnp.arange(P, dtype=jnp.int32)[None, None, :]).reshape(S, MP * P)
    return jnp.transpose(pool[idx], (0, 2, 1, 3))


def paged_insert(pool, new, lengths, page_table, page_size: int, write=None):
    """Append k tokens' K or V rows into the paged pool: ``new``
    [S, H, k, d] written at logical positions ``lengths[s] + i`` through
    the page table. ``write`` [S] gates rows exactly like
    :func:`cache_insert` (gated rows scatter back the old value — a
    no-op even on the clamped/zero page). The scatter touches O(S*k*H*d)
    bytes; with donated pool buffers XLA updates the pool in place."""
    lengths = jnp.asarray(lengths).astype(jnp.int32)
    new = jnp.asarray(new)
    S, H, k, d = new.shape
    pos = lengths[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    rows = paged_positions(page_table, pos, page_size).reshape(S * k)
    upd = jnp.transpose(new, (0, 2, 1, 3)).reshape(S * k, H, d) \
        .astype(pool.dtype)
    if write is not None:
        keep = jnp.repeat(jnp.asarray(write).astype(bool), k)[:, None, None]
        upd = jnp.where(keep, upd, pool[rows])
    return pool.at[rows].set(upd)


def page_rows(pages, page_size: int):
    """Token rows covering WHOLE pages: ``pages`` [n] page ids ->
    [n * page_size] int32 rows — the index form shared by
    :func:`page_export` / :func:`page_import` (ISSUE 18). Page id 0
    (padding in a fixed-size migration bucket) resolves to the reserved
    zero page; importers gate those rows off."""
    P = int(page_size)
    pages = jnp.asarray(pages).astype(jnp.int32)
    return (pages[:, None] * P
            + jnp.arange(P, dtype=jnp.int32)[None, :]).reshape(-1)


def page_export(pool, rows):
    """Gather whole pages out of one layer's pool in ONE device call:
    ``pool`` [NP*P, H, d], ``rows`` [n*P] -> [n*P, H, d] payload block
    (ISSUE 18 KV-page migration — never a device round-trip per page)."""
    return pool[rows]


def page_import(pool, rows, payload, gate):
    """Scatter whole pages into one layer's pool in ONE device call.
    ``gate`` [n*P] bool follows the write-gate contract of
    :func:`paged_insert`: gated-off rows (bucket padding pointing at the
    zero page) scatter back the value they gathered — a no-op — so an
    import can never corrupt the zero page or a page another stream
    holds."""
    upd = jnp.asarray(payload).astype(pool.dtype)
    upd = jnp.where(jnp.asarray(gate).astype(bool)[:, None, None],
                    upd, pool[rows])
    return pool.at[rows].set(upd)


# --------------------------------------------------------------------------
# multi-query decode: verify k speculated tokens in ONE step (ISSUE 12)
# --------------------------------------------------------------------------

def reference_decode_multiquery(q, k, v, lengths, scale=None):
    """Quadratic reference for the speculative Tq=k verify window: query
    i sits at global position ``lengths[b] + i`` and attends cache
    columns ``< lengths[b] + 1 + i`` (its own just-appended token
    included) — causal WITHIN the window, full visibility of the prefix.
    Shares :func:`reference_attention`'s f32 numerics."""
    C = k.shape[2]
    Tq = q.shape[2]
    lengths = jnp.asarray(lengths).astype(jnp.int32)
    col = jnp.arange(C, dtype=jnp.int32)[None, None, :]
    row = jnp.arange(Tq, dtype=jnp.int32)[None, :, None]
    valid = col < lengths[:, None, None] + 1 + row
    bias = jnp.where(valid, jnp.float32(0.0), jnp.float32(_NEG))[:, None]
    return reference_attention(q, k, v, bias=bias, scale=scale)


def decode_multiquery_attention(q, k, v, lengths, scale=None, *,
                                block_k: Optional[int] = None,
                                interpret: bool = False, page: int = 0):
    """Fused multi-query decode: the window-causal kernel at ``bq = Tq=k``
    (forward only — verification is inference) streaming the cache in
    ``block_k`` tiles with the per-row base length driving the in-kernel
    causal mask. ``q`` [B, H, k, d]; ``k``/``v`` [B, H, C, d];
    ``lengths`` [B] = valid cache entries BEFORE the k-token window (the
    window's own rows already appended at ``lengths .. lengths+k-1``)."""
    if q.ndim != 4 or q.shape[2] < 1:
        raise ValueError(f"decode_multiquery wants q [B,H,k,d]; got "
                         f"{q.shape}")
    B, H, Tq, d = q.shape
    C = k.shape[2]
    if k.shape != (B, H, C, d) or v.shape != (B, H, C, d):
        raise ValueError(f"q/cache shapes disagree: {q.shape} {k.shape} "
                         f"{v.shape}")
    if block_k is None:
        from . import autotune as _autotune
        tuned = _autotune.get_blocks(
            Tq, C, d, q.dtype, True, decode=True, page=page,
            concrete=not isinstance(q, jax.core.Tracer))
        bk = tuned[1] if tuned is not None else None
        if bk is not None and C % bk:
            bk = pick_block(C)
    else:
        bk = pick_block(C, block_k)
    if bk is None:
        raise ValueError(f"cache length {C} does not tile into decode "
                         "blocks; bucket the cache to a power of two")
    if not fits_vmem_attention(Tq, bk, d, np.dtype(q.dtype).itemsize):
        raise ValueError(f"multi-query decode tiles exceed the VMEM "
                         f"budget (Tq={Tq}, bk={bk}, d={d})")
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    o = _mq_impl(q.reshape(B * H, Tq, d), k.reshape(B * H, C, d),
                 v.reshape(B * H, C, d),
                 jnp.asarray(lengths).astype(jnp.int32), float(scale), H, bk,
                 bool(interpret))
    return o.reshape(B, H, Tq, d)


# --------------------------------------------------------------------------
# dispatch: mode + counters (zero-silent-fallback observability)
# --------------------------------------------------------------------------

_COUNTER_KEYS = ("fused", "fallback_mode", "fallback_platform",
                 "fallback_shape", "fallback_bias", "fallback_dtype",
                 "fallback_vmem",
                 # decode decisions ride the same registry counter so the
                 # serving dispatch mix shows up on the same /metrics family
                 "decode_fused", "decode_fallback_mode",
                 "decode_fallback_platform", "decode_fallback_shape",
                 "decode_fallback_dtype", "decode_fallback_vmem",
                 # ISSUE 12: Tq>1 decisions split out of the one
                 # decode_fallback_shape slug — a query-bank reference
                 # route (by design) is distinguishable from the
                 # speculative verify either taking its fused Tq=k path
                 # (decode_multiquery) or silently losing it
                 # (decode_multiquery_fallback)
                 "decode_fallback_multiquery", "decode_multiquery",
                 "decode_multiquery_fallback",
                 # a trace that GSPMD partitions over a mesh
                 # (pallas_kernels.gspmd_trace: ParallelWrapper's step, a
                 # serving engine's lowering; ISSUE 17). GSPMD cannot split
                 # a Mosaic kernel, so the decode dispatchers open a
                 # shard_map over the model axis when the heads divide it,
                 # and everything else takes the reference einsum path,
                 # which GSPMD partitions itself. Both counted.
                 "decode_tp_shard_map", "decode_multiquery_tp_shard_map",
                 "fallback_gspmd", "decode_fallback_gspmd",
                 "decode_multiquery_fallback_gspmd")
# dispatch decisions live in the process-wide MetricsRegistry (ISSUE 6):
# one counter, labeled by decision, so `GET /metrics` exposes the
# fused-vs-fallback mix; counters()/reset_counters() below are the
# pre-registry views tier-1 asserts against.
from ..runtime import telemetry as _tel  # noqa: E402  (stdlib-only import)

_DISPATCH = _tel.counter(
    "flash_attention.dispatch",
    "attention dispatch decisions at trace time (fused vs fallback_*)")
#: which tiling a traced one-shot site took (:func:`tiling_kind`), one count
#: a site like the dispatch decisions
_TILING = _tel.counter(
    "flash_attention.tiling",
    "tiling of a traced flash-attention site (whole_row vs blocked)")
_state = {"mode": os.environ.get("DL4J_TPU_FLASH_ATTENTION", "auto")}
_FUSABLE_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


def mode() -> str:
    return _state["mode"]


def set_mode(m: str) -> str:
    """"auto" (TPU -> kernel, elsewhere -> reference), "force" (kernel
    everywhere — Pallas interpret off-TPU; how the CPU tier-1 suite
    exercises the kernel), "off" (reference everywhere). Returns the
    previous mode so tests can restore it.

    The mode is consulted at TRACE time: functions already jit-compiled
    (an engine's cached train step / output fn, a warmed serving
    executable) keep whichever path was traced into them, with no counter
    bump on later executions. Flip the mode BEFORE building/tracing, or
    invalidate the model's compiled cache (``net._invalidate_compiled()``)
    after flipping."""
    if m not in ("auto", "force", "off"):
        raise ValueError(f"flash attention mode {m!r} not in "
                         "('auto', 'force', 'off')")
    old = _state["mode"]
    _state["mode"] = m
    return old


def _interpret() -> bool:
    """Interpret mode is asked for, never fallen into: ``force`` off-TPU
    (how the CPU tests reach the kernel code). On ``auto`` the off-TPU
    route is the counted reference path, and on TPU the kernel compiles."""
    return _state["mode"] == "force" and not _tpu_available()


def counters() -> dict:
    """Dispatch-decision counts. Decisions happen at TRACE time (shapes are
    static), so under jit each compiled call-site counts once, not once per
    execution — the right unit for "did the kernel path get taken". A view
    over the registry's ``flash_attention.dispatch{decision=}`` counter."""
    return {k: int(_DISPATCH.value(decision=k)) for k in _COUNTER_KEYS}


def reset_counters() -> None:
    _DISPATCH.zero()


def _route(q, k, v, bias) -> Optional[str]:
    """None = fuse; otherwise the fallback counter key."""
    if _state["mode"] == "off":
        return "fallback_mode"
    if _state["mode"] != "force" and not _tpu_available():
        return "fallback_platform"
    if q.ndim != 4 or k.shape != v.shape or q.shape[:2] != k.shape[:2] \
            or q.shape[-1] != k.shape[-1]:
        return "fallback_shape"
    if q.dtype not in _FUSABLE_DTYPES:
        return "fallback_dtype"
    if bias is not None and _key_bias(bias, q.shape[0], k.shape[2]) is None:
        return "fallback_bias"
    tq, tk, has_bias = q.shape[2], k.shape[2], bias is not None
    if default_blocks(tq, tk, q.shape[-1], np.dtype(q.dtype).itemsize,
                      has_bias) is None:
        tiles = next(_tilings(tq, tk, has_bias), None) is not None
        return "fallback_vmem" if tiles else "fallback_shape"
    return None


def attention(q, k, v, bias=None, scale: Optional[float] = None):
    """Guarded attention dispatch: the flash kernel when the route is clear,
    the f32-softmax reference path otherwise. Layers and the SameDiff
    ``attention.fused_sdpa`` op both enter here.

    In a trace that GSPMD partitions (``pallas_kernels.gspmd_trace``) the
    reference einsum path is taken unconditionally: GSPMD partitions the
    batch- or head-sharded contractions itself, which it cannot do to the
    kernel, and the decision is counted ``fallback_gspmd`` (not silent)."""
    if _partitioned() is not None:
        _DISPATCH.inc(decision="fallback_gspmd")
        return reference_attention(q, k, v, bias, scale)
    reason = _route(q, k, v, bias)
    if reason is None:
        _DISPATCH.inc(decision="fused")
        return flash_attention(q, k, v, bias, scale,
                               interpret=_interpret())
    _DISPATCH.inc(decision=reason)
    return reference_attention(q, k, v, bias, scale)


def _route_decode(q, k, v) -> Optional[str]:
    """None = fuse the decode kernel; otherwise the fallback counter key
    (every key prefixed ``decode_`` so the serving mix is separable from
    the one-shot dispatch on the same registry counter)."""
    if _state["mode"] == "off":
        return "decode_fallback_mode"
    if _state["mode"] != "force" and not _tpu_available():
        return "decode_fallback_platform"
    if q.ndim != 4 or q.shape[2] != 1 or k.shape != v.shape or \
            q.shape[:2] != k.shape[:2] or q.shape[-1] != k.shape[-1]:
        return "decode_fallback_shape"
    if q.dtype not in _FUSABLE_DTYPES:
        return "decode_fallback_dtype"
    bk = pick_kv_block(k.shape[2], has_bias=True)
    if bk is None:
        return "decode_fallback_shape"
    if not fits_vmem_attention(1, bk, q.shape[-1],
                               np.dtype(q.dtype).itemsize):
        return "decode_fallback_vmem"
    return None


def _decode_dispatch_local(q, k, v, lengths, scale=None, page: int = 0):
    """The per-device decode dispatch body: single-query flash kernel
    when the route is clear, f32-softmax reference otherwise. Called
    directly from inside the shard_map inner, where the kernel sees one
    device's block — the trace is still a partitioned one there and
    re-entering :func:`decode_dispatch` would recurse."""
    if q.ndim == 4 and q.shape[2] == 1:
        reason = _route_decode(q, k, v)
    elif q.ndim == 4 and q.shape[2] > 1:
        reason = "decode_fallback_multiquery"
    else:
        reason = "decode_fallback_shape"
    if reason is None:
        _DISPATCH.inc(decision="decode_fused")
        return decode_attention(q, k, v, lengths, scale, page=page,
                                interpret=_interpret())
    _DISPATCH.inc(decision=reason)
    C = k.shape[2]
    bias = length_bias(lengths, C)[:, None, None, :]
    return reference_attention(q, k, v, bias=bias, scale=scale)


def _head_shards(q) -> Optional[tuple]:
    """In a partitioned trace whose mesh has a model axis that divides
    the heads of ``q`` [B, H, *, d]: ``(mesh, axis)``, else None."""
    mesh, axis = _partitioned()
    if axis is None or q.shape[1] % int(mesh.shape[axis]):
        return None
    return mesh, axis


def _tp_head_shard(local_fn, shards, q, k, v, lengths, scale, page):
    """Run a per-device dispatch body under shard_map with heads (axis 1
    of the [B, H, *, d] operands) split over the model axis. ``lengths``
    stays replicated; softmax is per-head so no cross-shard collective
    is needed (check_vma=False: the head axis is genuinely sharded)."""
    mesh, axis = shards
    spec4 = P(None, axis, None, None)

    def inner(q_, k_, v_, lengths_):
        return local_fn(q_, k_, v_, lengths_, scale=scale, page=page)

    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(spec4, spec4, spec4, P()),
                         out_specs=spec4, check_vma=False)(q, k, v, lengths)


def decode_dispatch(q, k, v, lengths, scale=None, page: int = 0):
    """Guarded decode dispatch: the single-query flash kernel when the
    route is clear, the f32-softmax reference otherwise. The KV-cache
    layers and the SameDiff ``attention.cached_sdpa`` op both enter here.
    ``q`` with Tq > 1 (e.g. LearnedSelfAttention's query bank — uniform
    visibility over the valid cache, NOT the speculative verify's causal
    window) takes the reference path, counted under its own
    ``decode_fallback_multiquery`` slug (ISSUE 12 satellite) so it never
    blends with genuine shape failures or the verify path's decisions.

    In a partitioned trace (``pallas_kernels.gspmd_trace``, ISSUE 17):
    heads divisible by the model-axis size run the per-shard body under
    ``shard_map`` (``decode_tp_shard_map``); otherwise the reference
    einsum, which GSPMD partitions (``decode_fallback_gspmd``). Both
    counted."""
    if _partitioned() is not None and q.ndim == 4:
        shards = _head_shards(q)
        if shards is not None:
            _DISPATCH.inc(decision="decode_tp_shard_map")
            return _tp_head_shard(_decode_dispatch_local, shards,
                                  q, k, v, lengths, scale, page)
        _DISPATCH.inc(decision="decode_fallback_gspmd")
        C = k.shape[2]
        bias = length_bias(lengths, C)[:, None, None, :]
        return reference_attention(q, k, v, bias=bias, scale=scale)
    return _decode_dispatch_local(q, k, v, lengths, scale=scale, page=page)


def _route_multiquery(q, k, v) -> Optional[str]:
    """None = fuse the Tq=k window-causal verify kernel; otherwise the
    single ``decode_multiquery_fallback`` slug — the signal the ISSUE 12
    satellite asks for: speculative verify silently losing its fused
    path is one visible number on ``/metrics``."""
    if _state["mode"] == "off":
        return "decode_multiquery_fallback"
    if _state["mode"] != "force" and not _tpu_available():
        return "decode_multiquery_fallback"
    if q.ndim != 4 or q.shape[2] < 1 or k.shape != v.shape or \
            q.shape[:2] != k.shape[:2] or q.shape[-1] != k.shape[-1]:
        return "decode_multiquery_fallback"
    if q.dtype not in _FUSABLE_DTYPES:
        return "decode_multiquery_fallback"
    bk = pick_block(k.shape[2])
    if bk is None:
        return "decode_multiquery_fallback"
    if not fits_vmem_attention(q.shape[2], bk, q.shape[-1],
                               np.dtype(q.dtype).itemsize):
        return "decode_multiquery_fallback"
    return None


def _decode_multiquery_local(q, k, v, lengths, scale=None, page: int = 0):
    """Per-device multi-query verify dispatch body (see
    :func:`_decode_dispatch_local` for why the TP wrapper calls this
    directly)."""
    reason = _route_multiquery(q, k, v)
    if reason is None:
        _DISPATCH.inc(decision="decode_multiquery")
        return decode_multiquery_attention(q, k, v, lengths, scale,
                                           page=page,
                                           interpret=_interpret())
    _DISPATCH.inc(decision=reason)
    return reference_decode_multiquery(q, k, v, lengths, scale=scale)


def decode_multiquery_dispatch(q, k, v, lengths, scale=None, page: int = 0):
    """Guarded multi-query decode dispatch (speculative verify, ISSUE
    12): the window-causal Tq=k kernel when the route is clear, the
    reference path with an explicit per-query bias otherwise. ``lengths``
    [B] counts valid cache entries BEFORE the k-token window. Every
    decision is counted (``decode_multiquery`` vs
    ``decode_multiquery_fallback``) — the tier-1 dispatch asserts and
    ``/metrics`` both see a verify that lost its fused path.

    Routing in a partitioned trace mirrors :func:`decode_dispatch`
    (``decode_multiquery_tp_shard_map`` /
    ``decode_multiquery_fallback_gspmd``)."""
    if _partitioned() is not None and q.ndim == 4:
        shards = _head_shards(q)
        if shards is not None:
            _DISPATCH.inc(decision="decode_multiquery_tp_shard_map")
            return _tp_head_shard(_decode_multiquery_local, shards,
                                  q, k, v, lengths, scale, page)
        _DISPATCH.inc(decision="decode_multiquery_fallback_gspmd")
        return reference_decode_multiquery(q, k, v, lengths, scale=scale)
    return _decode_multiquery_local(q, k, v, lengths, scale=scale,
                                    page=page)


@register("attention.fused_sdpa", category="attention")
def fused_sdpa(q, k, v, bias=None, scale: float = 1.0):
    """Fused scaled-dot-product attention graph op: the rewrite target of
    the SameDiff attention-pattern fusion pass (``autodiff/fusion.py``).
    Semantics: softmax((q @ k^T) * scale + bias, axis=-1) @ v — exactly the
    imported ``batch_matmul -> scale -> (mask add) -> softmax ->
    batch_matmul`` chain it replaces, with the softmax in f32. Dispatches
    to the flash kernel for [B,H,T,d] operands on TPU."""
    return attention(q, k, v, bias=bias, scale=float(scale))


@register("attention.cached_sdpa", category="attention",
          differentiable=False)
def cached_sdpa(q, k_new, v_new, k_cache, v_cache, lengths,
                scale: float = 1.0):
    """KV-cached decode-step attention graph op: the rewrite target of the
    SameDiff decode pass (``autodiff/decode.py``), replacing an
    ``attention.fused_sdpa`` site in the one-token decode replay.

    ``q``/``k_new``/``v_new``: this step's projections, [B, H, 1, d];
    ``k_cache``/``v_cache``: [B, H, C, d] HBM cache at its bucket length;
    ``lengths``: [B] valid entries per row BEFORE this token. Appends
    (k_new, v_new) at position ``lengths``, attends the query over the
    ``lengths + 1`` valid entries, and returns
    ``(y, k_cache', v_cache')`` so the cache state threads through the
    graph replay. Inference-only (no VJP — decode never trains).

    The CALLER must keep ``lengths < C``: an out-of-range position
    clamps (XLA slice semantics) and would overwrite the last cache row
    — ``autodiff.decode.DecodeGraph.decode_step`` raises host-side when
    the cache is full, and the serving batcher grows the bucket first."""
    lengths = jnp.asarray(lengths)
    kc = cache_insert(k_cache, k_new, lengths)
    vc = cache_insert(v_cache, v_new, lengths)
    y = decode_dispatch(q, kc, vc, lengths + 1, scale=float(scale))
    return y, kc, vc


@register("attention.paged_sdpa", category="attention",
          differentiable=False)
def paged_sdpa(q, k_new, v_new, k_pool, v_pool, page_table, lengths,
               scale: float = 1.0, page_size: int = 16):
    """Paged-KV decode-step attention graph op (ISSUE 12): the paged twin
    of ``attention.cached_sdpa``, the rewrite target of
    ``autodiff.decode.rewrite_for_decode(..., paged=True)``.

    ``q``/``k_new``/``v_new``: this step's projections, [B, H, Tq, d]
    (Tq = 1 for plain decode, k for a speculative verify window);
    ``k_pool``/``v_pool``: [n_pages*page_size, H, d] token-row pools;
    ``page_table``: [B, MP] int32 physical page ids; ``lengths``: [B]
    valid entries per row BEFORE this window. Appends the window's rows
    through the page table, attends (single-query length-masked, or
    window-causal for Tq > 1), and returns ``(y, k_pool', v_pool')``.
    The CALLER keeps ``lengths + Tq <= MP*page_size`` and forks shared
    pages first (copy-on-write lives host-side in the pool allocator)."""
    lengths = jnp.asarray(lengths)
    kp = paged_insert(k_pool, k_new, lengths, page_table, page_size)
    vp = paged_insert(v_pool, v_new, lengths, page_table, page_size)
    kf = paged_gather(kp, page_table, page_size)
    vf = paged_gather(vp, page_table, page_size)
    if q.shape[2] == 1:
        y = decode_dispatch(q, kf, vf, lengths + 1, scale=float(scale),
                            page=int(page_size))
    else:
        y = decode_multiquery_dispatch(q, kf, vf, lengths,
                                       scale=float(scale),
                                       page=int(page_size))
    return y, kp, vp
