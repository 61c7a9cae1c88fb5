"""Causal and sliding-window attention over grouped KV heads without a
``[T, T]`` score matrix, and the rotary embeddings that go with it.

Two paths, and :func:`causal_attention` counts which a traced site took
(``attention.dispatch``). On a TPU a sequence that tiles goes to the blocked
Pallas kernels of ``ops/flash_attention.py`` under a static
:class:`~.flash_attention.BlockMask` (:func:`causal_flash`): scores stay in
VMEM, a block pair the mask closes is neither computed nor fetched, the
query heads of a KV head read its rows through the index map, and the
backward keeps the output and one ``[rows, T]`` logsumexp. Everywhere else
(the CPU in ``auto``, a GSPMD-partitioned trace, a sequence of one block) a
blocked XLA path: queries are cut into blocks, each block sees only the key
blocks its mask leaves open, and every block is a ``jax.checkpoint`` so the
backward pass holds one block's scores at a time. A mask that is data
(``select=``: the keys a learned indexer chose, ``ops/sparse_attention.py``)
goes the same way: on the kernels it is an 8-bit operand whose tile each
scores tile reads beside the causal cut, the query heads of a KV head
stacked in one tile so that it is fetched once for them; on the XLA path
every causal block reads its slice of it.

A caller inside a recomputed segment (``nn/memory.py`` ``checkpoint``) may ask
with ``keep=True`` that the result be kept for the backward pass, which reads
nothing else of this forward: it is tagged ``memory.KEPT`` (on the kernel
path the logsumexp with it), and the segment's recomputation then holds no
score product. ``attention.kept`` counts what a traced site did.

Layout: ``q`` ``[B, T, H, d]``, ``k`` ``[B, T, KV, d]``, ``v`` ``[B, T, KV,
dv]``; query head ``i`` reads KV head ``i // (H // KV)``. ``dv`` need not be
``d`` (latent attention scores over 192 and carries values of 128).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from . import flash_attention as _fa
from .pallas_kernels import (available as _tpu_available,
                             partitioned as _partitioned)
from ..runtime import telemetry as _tel

_NEG = -1e30

_DISPATCH = _tel.counter(
    "attention.dispatch",
    "causal attention sites by mask kind and the path taken, once a traced "
    "site")
_KEPT = _tel.counter(
    "attention.kept",
    "causal attention sites by mask kind and whether a recomputed segment "
    "keeps the output for its backward pass, once a traced site")


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


def _block(q, k, v, q0: int, k0: int, window: Optional[int], select=None):
    """One query block against the keys its mask leaves open. ``q``
    ``[..., G, bq, d]``, ``k`` / ``v`` ``[..., nk, d]``; ``q0`` / ``k0`` the
    position of the first query / key (``k0`` may be negative: keys before
    position 0 are padding). ``select`` ``[..., bq, nk]`` bool: the keys a
    selection leaves open to each query, the same for the ``G`` heads."""
    d = q.shape[-1]
    s = jnp.einsum("...gqd,...kd->...gqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    qpos = q0 + jnp.arange(q.shape[-2])[:, None]
    kpos = k0 + jnp.arange(k.shape[-2])[None, :]
    open_ = (kpos <= qpos) & (kpos >= 0)
    if window is not None:
        open_ &= kpos > qpos - window
    if select is not None:
        open_ = open_ & select[..., None, :, :]
    p = _softmax(jnp.where(open_, s, _NEG))
    return jnp.einsum("...gqk,...kd->...gqd", p.astype(v.dtype), v)


def _rows_full(q, k, v, block: int, window: Optional[int]):
    """``q`` ``[G, T, d]``, ``k`` / ``v`` ``[T, d]``: query blocks one after
    another, each over the keys from the first block its mask reaches to its
    own. A block's ``jax.checkpoint`` is handed the whole row, the caller's
    own array, and cuts its slices inside, so the backward pass keeps the
    row once and no slice of it (as new arrays the slices were 4.5x the row
    at 8 causal blocks)."""
    T = q.shape[1]
    outs = []
    for q0 in range(0, T, block):
        k0 = 0 if window is None else \
            max(0, (q0 - window + 1) // block * block)
        k1 = q0 + block
        fn = jax.checkpoint(
            lambda a, b, c, q0=q0, k0=k0, k1=k1: _block(
                a[:, q0:k1], b[k0:k1], c[k0:k1], q0, k0, window))
        outs.append(fn(q, k, v))
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


def _rows_select(q, k, v, select, row, block: int):
    """:func:`_rows_full` under a selection: ``select`` ``[B, T, T]`` bool is
    the whole layer's mask and ``row`` the sequence this KV row belongs to.
    A block pair is skipped only where causality closes it; the others read
    their slice of the mask, cut inside the block's ``jax.checkpoint`` so
    that the backward pass holds the one mask and no slice of it."""
    T = q.shape[1]
    outs = []
    for q0 in range(0, T, block):
        k1 = q0 + block
        fn = jax.checkpoint(
            lambda a, b, c, m, r, q0=q0, k1=k1: _block(
                a[:, q0:k1], b[:k1], c[:k1], q0, 0, None,
                jax.lax.dynamic_slice(m, (r, q0, 0), (1, block, k1))[0]))
        outs.append(fn(q, k, v, select, row))
    return jnp.concatenate(outs, axis=1)


# --------------------------------------------------------------------- keep
def _memory():
    from ..nn import memory                     # nn imports ops, not back
    return memory


def _tag(a):
    return checkpoint_name(a, _memory().KEPT)


def _keeps(kind: str, keep: bool) -> bool:
    """Whether this site's result is kept, counted once a traced site. The
    caller asks from its shapes; nothing is kept outside a recomputed
    segment, where nothing is computed twice."""
    if not keep:
        _KEPT.inc(kind=kind, decision="recomputed", why="wide")
    elif not _memory().recomputing():
        _KEPT.inc(kind=kind, decision="recomputed", why="no_policy")
    else:
        _KEPT.inc(kind=kind, decision="kept")
        return True
    return False


# ------------------------------------------------------------------- kernel
#: what a grid step costs, in pairs of (query, key): about 0.35 us, the time
#: the products and the softmax of a 256 x 256 tile take (PERF.md, PR 36)
_STEP_PAIRS = 256 * 256
_BLOCKS = (1024, 512, 256, 128)


def causal_blocks(t: int, d: int, dv: int, window: Optional[int],
                  itemsize: int = 2, stacked: int = 0):
    """(block_q, block_k) for the masked kernels, from the shape and the
    mask: of the multiples of 128 that divide ``t`` and fit VMEM, the pair
    that costs least in pairs computed (every block the mask leaves open is
    computed whole) plus grid steps (the closed ones are skipped, not free).
    ``flash_attention.default_blocks`` wants the most keys that fit, which
    under a causal mask are mostly closed pairs. ``stacked``: under a mask
    that is data the tile stacks that many query heads, ``stacked *
    block_q`` rows beside the mask's 8-bit ``[block_q, block_k]`` tile, and
    a grid step costs as many steps as it stacks heads (it repeats the mask
    tile for each and gathers their statistics): the order this gives is the
    chip's over the four tilings measured at Keye's 8 heads a KV head
    (PERF.md, PR 42). None where nothing tiles."""
    heads = max(stacked, 1)

    def cost(blocks):
        mask = _fa.BlockMask(*blocks, t, window)
        return heads * (mask.open_blocks() * mask.bq * mask.bk
                        + mask.nq * mask.key_span * _STEP_PAIRS)

    fit = [(bq, bk) for bq in _BLOCKS for bk in _BLOCKS
           if t % bq == 0 and t % bk == 0
           and _fa.fits_vmem_attention(heads * bq, bk, max(d, dv), itemsize,
                                       mask_rows=bq if stacked else 0)]
    return min(fit, key=cost, default=None)


def _kv_map(mask, group: int):
    """Index map of a key or value block under the (query rows, query
    blocks, key span) grid: query row ``b`` reads KV row ``b // group``, and
    grid position ``j`` is key block ``first_key(i) + j``. Past the last
    block a query block reaches the index stays put, so the pipeline fetches
    nothing for a step that computes nothing."""
    def kv(b, i, j):
        return (jax.lax.div(b, group), _key_block(mask, i, j), 0)
    return kv


def _key_block(mask, i, j):
    return jnp.minimum(mask.first_key(i) + j, mask.last_key(i))


def _params(pltpu, mask, d, dv, dtype, lead=1, selected=False):
    """``lead``: the query heads a query-side block stacks; ``selected``: a
    mask tile is fetched beside the keys."""
    return _fa._compiler_params(pltpu, vmem_bytes=_fa.vmem_bytes_attention(
        lead * mask.bq, mask.bk, max(d, dv), np.dtype(dtype).itemsize,
        mask_rows=mask.bq if selected else 0))


def _stacking(q3, k3, mask, group, sel):
    """Under a mask that is data (``sel`` ``[B, T, T]``): the grid walks KV
    rows, a query-side block holds the ``group`` query heads of one (the
    mask's tile is fetched once for them all), and the mask's index map
    reads the sequence of the row. -> (grid rows, heads a block, group of
    the grid, the mask's spec or None)."""
    if sel is None:
        return q3.shape[0], 1, group, None
    pl, _ = _fa._load_pallas()
    rows_per_seq = k3.shape[0] // sel.shape[0]
    return (k3.shape[0], group, 1, pl.BlockSpec(
        (1, mask.bq, mask.bk), lambda b, i, j: (
            jax.lax.div(b, rows_per_seq), i, _key_block(mask, i, j))))


def _fwd_call(q3, k3, v3, mask, group, scale, interpret, sel=None):
    pl, pltpu = _fa._load_pallas()
    R, T, d = q3.shape
    dv = v3.shape[-1]
    bq, bk = mask.bq, mask.bk
    rows, lead, group, sel_spec = _stacking(q3, k3, mask, group, sel)
    kv = _kv_map(mask, group)
    tile = lead * bq
    ins = (q3, k3, v3) + (() if sel is None else (sel,))
    return pl.pallas_call(
        functools.partial(_fa._fwd_kernel, scale=scale, nk=mask.key_span,
                          has_bias=False, mask=mask, stacked=sel is not None),
        grid=(rows, mask.nq, mask.key_span),
        in_specs=[pl.BlockSpec((lead, bq, d), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, bk, d), kv),
                  pl.BlockSpec((1, bk, dv), kv)]
        + ([] if sel is None else [sel_spec]),
        out_shape=(jax.ShapeDtypeStruct((R, T, dv), q3.dtype),
                   jax.ShapeDtypeStruct((R, 1, T), jnp.float32)),
        out_specs=(pl.BlockSpec((lead, bq, dv), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((lead, 1, bq), lambda b, i, j: (b, 0, i))),
        scratch_shapes=[pltpu.VMEM((tile, _fa._LANES), jnp.float32),
                        pltpu.VMEM((tile, _fa._LANES), jnp.float32),
                        pltpu.VMEM((tile, dv), jnp.float32)],
        compiler_params=_params(pltpu, mask, d, dv, q3.dtype, lead,
                                sel is not None),
        interpret=interpret,
        name="causal_flash_fwd",
    )(*ins)


def _bwd_call(q3, k3, v3, lse, di, do, mask, group, scale, interpret,
              sel=None):
    pl, pltpu = _fa._load_pallas()
    R, T, d = q3.shape
    dv = v3.shape[-1]
    bq, bk = mask.bq, mask.bk
    rows, lead, group, sel_spec = _stacking(q3, k3, mask, group, sel)
    params = _params(pltpu, mask, d, dv, q3.dtype, lead, sel is not None)
    kv = _kv_map(mask, group)
    tile = lead * bq
    by_q = lambda b, i, j: (b, i, 0)
    row = pl.BlockSpec((lead, 1, bq), lambda b, i, j: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(_fa._bwd_dq_kernel, scale=scale, nk=mask.key_span,
                          has_bias=False, mask=mask, stacked=sel is not None),
        grid=(rows, mask.nq, mask.key_span),
        in_specs=[pl.BlockSpec((lead, bq, d), by_q),
                  pl.BlockSpec((1, bk, d), kv),
                  pl.BlockSpec((1, bk, dv), kv)]
        + ([] if sel is None else [sel_spec])
        + [row, row,
           pl.BlockSpec((lead, bq, dv), by_q)],
        out_shape=jax.ShapeDtypeStruct((R, T, d), q3.dtype),
        out_specs=pl.BlockSpec((lead, bq, d), by_q),
        scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32),
                        pltpu.VMEM((tile, _fa._LANES), jnp.float32),
                        pltpu.VMEM((tile, _fa._LANES), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="causal_flash_bwd_dq",
    )(*((q3, k3, v3) + (() if sel is None else (sel,)) + (lse, di, do)))

    # dk/dv grid: key blocks outer; inside, the group's heads and under each
    # the query blocks that reach the key block (the reduction axis)
    span = mask.query_span

    def head(b, t):
        return b * group + jax.lax.div(t, span)

    def block(j, t):
        return jnp.minimum(mask.first_query(j) + jax.lax.rem(t, span),
                           mask.last_query(j))

    by_qt = lambda b, j, t: (head(b, t), block(j, t), 0)
    row_t = pl.BlockSpec((lead, 1, bq), lambda b, j, t: (head(b, t), 0,
                                                          block(j, t)))
    by_k = lambda b, j, t: (b, j, 0)
    ins = (q3, k3, v3, lse, di, do)
    sel_t = []
    if sel is not None:
        # the transposed mask, [keys, queries]: the tile is [bk, bq] here
        rows_per_seq = k3.shape[0] // sel.shape[0]
        sel_t = [pl.BlockSpec((1, bk, bq), lambda b, j, t: (
            jax.lax.div(b, rows_per_seq), j, block(j, t)))]
        ins = ins[:3] + (jnp.swapaxes(sel, 1, 2),) + ins[3:]
    dk, dv_ = pl.pallas_call(
        functools.partial(_fa._bwd_dkv_masked_kernel, scale=scale, mask=mask,
                          group=group, stacked=sel is not None),
        grid=(rows // group, mask.nk, group * span),
        in_specs=[pl.BlockSpec((lead, bq, d), by_qt),
                  pl.BlockSpec((1, bk, d), by_k),
                  pl.BlockSpec((1, bk, dv), by_k)]
        + sel_t
        + [row_t, row_t,
           pl.BlockSpec((lead, bq, dv), by_qt)],
        out_shape=(jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)),
        out_specs=(pl.BlockSpec((1, bk, d), by_k),
                   pl.BlockSpec((1, bk, dv), by_k)),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="causal_flash_bwd_dkv",
    )(*ins)
    return dq, dk, dv_


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q3, k3, v3, sel, mask, group, scale, interpret, keep):
    return _fwd_call(q3, k3, v3, mask, group, scale, interpret, sel)[0]


def _flash_fwd(q3, k3, v3, sel, mask, group, scale, interpret, keep):
    o, lse = _fwd_call(q3, k3, v3, mask, group, scale, interpret, sel)
    if keep:
        # the backward kernels read both: the forward kernel leaves a
        # segment's recomputation only if neither has to be rebuilt
        o, lse = _tag(o), _tag(lse)
    return o, (q3, k3, v3, o, lse, sel)


def _flash_bwd(mask, group, scale, interpret, keep, res, do):
    q3, k3, v3, o, lse, sel = res
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    # the mask is data and takes no gradient
    return _bwd_call(q3, k3, v3, lse, di[:, None, :], do, mask, group, scale,
                     interpret, sel) + (None,)


_flash.defvjp(_flash_fwd, _flash_bwd)


def causal_flash(q, k, v, *, window: Optional[int] = None, blocks=None,
                 interpret: bool = False, keep: bool = False, select=None):
    """The masked kernels on ``q`` ``[B, H, T, d]``, ``k`` ``[B, KV, T, d]``,
    ``v`` ``[B, KV, T, dv]`` -> ``[B, H, T, dv]``. ``blocks``: (block_q,
    block_k), multiples of 128 that divide ``T`` (default:
    :func:`causal_blocks`). ``keep`` tags the output and the logsumexp
    ``memory.KEPT`` for a recomputing caller. ``select`` ``[B, T, T]``
    bool: a mask that is data, read by the kernels a tile at a time beside
    the causal cut (every row must keep a key open); the ``H // KV`` query
    heads of a KV head then share a tile. Raises ValueError where nothing
    tiles: callers go through :func:`causal_attention` for guarded
    dispatch."""
    B, H, T, d = q.shape
    KV, dv = k.shape[1], v.shape[-1]
    stacked = 0 if select is None else H // KV
    blocks = blocks or causal_blocks(T, d, dv, window,
                                     np.dtype(q.dtype).itemsize, stacked)
    if blocks is None or T % blocks[0] or T % blocks[1]:
        raise ValueError(f"a sequence of {T} does not tile into {blocks}")
    if select is not None:
        if window is not None:
            raise ValueError("a selection has no window")
        select = select.astype(jnp.int8)
    o = _flash(q.reshape(B * H, T, d), k.reshape(B * KV, T, d),
               v.reshape(B * KV, T, dv), select,
               _fa.BlockMask(*blocks, T, window), H // KV,
               1.0 / math.sqrt(d), bool(interpret), bool(keep))
    return o.reshape(B, H, T, dv)


def _xla_reason(q, group: int, T: int, d: int, dv: int,
                window: Optional[int], stacked: int = 0):
    """Why a sequence that tiles goes to the XLA path all the same, or None
    where the kernels take it. ``stacked``: the heads a tile stacks under a
    mask that is data."""
    if _partitioned() is not None:
        return "gspmd"
    if _fa.mode() == "off":
        return "mode"
    if _fa.mode() != "force" and not _tpu_available():
        return "platform"
    if q.dtype not in _fa._FUSABLE_DTYPES or T % _BLOCKS[-1]:
        return "shape"
    if causal_blocks(T, d, dv, window, np.dtype(q.dtype).itemsize,
                     stacked) is None:
        return "vmem"
    if group == 1 and _fa.mode() != "force":
        # one query head a KV head: XLA's blocks are [1, block, keys] there
        # and run their products at twice the rate of its grouped blocks.
        # On a v5e at 8,192 positions, forward + backward of 64 rows: XLA
        # 37.9 ms against the kernels' 40.9 at a scored width of 128, 42.0
        # against 59.0 at 192 (which fills one and a half MXU tiles); with 8
        # query heads a KV head 84.4 against 37.4 and 83.9 against 54.4
        # (PERF.md, PR 36)
        return "ungrouped"
    return None


def causal_attention(q, k, v, *, window: Optional[int] = None,
                     block: int = 1024, kind: Optional[str] = None,
                     keep: bool = False, select=None):
    """softmax(q k^T / sqrt(d) + mask) v with a causal mask and, with
    ``window``, key ``j`` open to query ``i`` only where ``i - window < j <=
    i``. -> ``[B, T, H, dv]``. ``block`` is the XLA path's: a sequence no
    longer than it (or not a multiple of it) is one block there
    (``decision=one_block``); one that tiles takes the kernels
    (``decision=kernel``; ``flash_attention.set_mode`` is the switch, and
    ``force`` runs them in interpret mode off the chip) or the blocked XLA
    path with the reason (``decision=blocked_rows | blocked_pairs``,
    ``why=platform | mode | gspmd | shape | vmem | ungrouped``).
    ``select`` ``[B, T, T]`` bool is a mask that is data (``ops/
    sparse_attention.py``): key ``j`` is open to query ``i`` only where it
    is set too, for every head alike, and every query keeps a key open. It
    is dispatched as any other site: on the kernels it is an operand read a
    tile at a time, with the query heads of a KV head in one tile; on the
    XLA path every causal block reads its slice. It has no window. ``kind``
    names the site in the ``attention.dispatch`` counter and the
    ``attn.<kind>`` scope (default: ``sparse`` with a selection, else
    ``full`` or ``window`` by the mask).
    ``keep``: inside a recomputed segment, keep the result for the backward
    pass (``attention.kept{decision=kept}``); a caller that finds its output
    too wide to keep passes False (``decision=recomputed, why=wide``), and
    outside such a segment there is nothing to keep (``why=no_policy``)."""
    B, T, H, d = q.shape
    KV, dv = k.shape[2], v.shape[-1]
    if H % KV:
        raise ValueError(f"{H} query heads do not divide over {KV} KV heads")
    G = H // KV
    if select is not None and window is not None:
        raise ValueError("a selection has no window: close the keys in the "
                         "mask")
    kind = kind or ("sparse" if select is not None
                    else "full" if window is None else "window")
    if window is not None:
        block = min(block, max(window, 128))
    keep = _keeps(kind, keep)
    if keep and select is not None:
        # the blocks' backward reads the mask: kept with the output, it takes
        # whatever made it (an indexer, a top-k) out of the recomputation too
        select = _tag(select)
    with jax.named_scope(f"attn.{kind}"):
        tiles = T > block and T % block == 0
        why = None if not tiles else _xla_reason(
            q, G, T, d, dv, window, 0 if select is None else G)
        if tiles and why is None:
            _DISPATCH.inc(kind=kind, decision="kernel")
            heads_first = lambda a: a.transpose(0, 2, 1, 3)
            out = causal_flash(heads_first(q), heads_first(k), heads_first(v),
                               window=window, interpret=_fa._interpret(),
                               keep=keep, select=select)
            return heads_first(out)
        qg = q.reshape(B, T, KV, G, d).transpose(0, 2, 3, 1, 4)  # [B,KV,G,T,d]
        kg, vg = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # [B,KV,T,d]
        if not tiles:
            _DISPATCH.inc(kind=kind, decision="one_block")
            out = _block(qg, kg, vg, 0, 0, window,
                         None if select is None else select[:, None])
        else:
            flat = lambda a: a.reshape((B * KV,) + a.shape[2:])
            xs = (flat(qg), flat(kg), flat(vg))
            if select is not None:
                _DISPATCH.inc(kind=kind, decision="blocked_rows", why=why)
                # with each KV row, the sequence whose mask it reads
                xs += (jnp.arange(B * KV) // KV,)
                rows = lambda a: _rows_select(*a[:3], select, a[3], block)
            elif window is not None and window <= block:
                _DISPATCH.inc(kind=kind, decision="blocked_pairs", why=why)
                rows = lambda a: _rows_window(*a, block, window)
            else:
                _DISPATCH.inc(kind=kind, decision="blocked_rows", why=why)
                rows = lambda a: _rows_full(*a, block, window)
            out = jax.lax.map(rows, xs)
            out = out.reshape(B, KV, G, T, dv)
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, dv)
        # the blocks' own checkpoints keep q, k, v, which the projections
        # rebuild cheaply: the output alone takes the forward out of the
        # segment's recomputation
        return _tag(out) if keep else out
