"""Sparse-expert routing and the grouped products of the experts held here.

The router scores every token against all ``num_experts`` and keeps its
``top_k``; the layer is told which experts it holds (``first``, ``count``)
and computes their part of the result for the tokens routed to them. No
capacity, no dropped token: the assignments to held experts are sorted by
expert and walked in chunks of ``chunk_rows`` rows, as many chunks as the
routing needs (a ``while`` with a trip count read from the routing), so a
routing that sends every token to one expert costs more chunks and loses
nothing. Inside a chunk the experts run as grouped matrix products
(``jax.lax.ragged_dot``: rows sorted by expert, one group per expert)
with the gate's activation a static argument (``swish``: SwiGLU, ``relu``:
ReGLU). What the experts held elsewhere would add is left out.

A chunk's rows go back to their tokens by a token-major segment sum, in the
forward (the weighted combine) and in the backward (``x``'s gradient): the
rows are put in ascending assignment id, so that a token's rows lie side by
side, and each token's are summed in float32 and written once
(:func:`segment_sum`; on the chip the ``moe_combine`` kernel). An XLA
scatter-add with repeated tokens took 8-10 ms a layer-step on a v5e at
16,384 x 2,560 (PERF.md, section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..runtime import telemetry as _tel
from . import activations as _act
from . import flash_attention as _fa
from .pallas_kernels import (available as _tpu_available,
                             partitioned as _partitioned)

_ROUTE = _tel.counter(
    "moe.route", "router sites by what the top-k is taken on, the scores "
    "(plain) or the scores plus a selection bias (biased), by how the "
    "router's outputs become scores (sigmoid, softmax), by the tensor it "
    "reads (own: the expert layer's input, early: the layer's input before "
    "attention) and by the experts' activation (swish, relu), once a traced "
    "site")
_COMBINE = _tel.counter(
    "moe.combine", "expert sites by the path of the token-major segment sum "
    "that returns the held rows to their tokens, in the forward pass (the "
    "weighted combine) and the backward (x's gradient): the moe_combine "
    "kernel, or its jnp form with the reason (platform, mode, gspmd, shape, "
    "vmem), once a traced site and pass")

#: tokens a grid step of the kernel writes: one MXU tile of rows
_TOKEN_BLOCK = 128


def route(x, w_router, top_k: int, scale: float, select_bias=None,
          scoring: str = "sigmoid", *, reads: str = "own",
          act: str = "swish"):
    """Scores in float32 over all experts, the ``top_k`` largest per token,
    weights ``scale * s / sum(s)`` over the chosen. ``scoring``: ``sigmoid``
    of each router output, or ``softmax`` over all of them (the weights are
    then the probabilities renormalised over the chosen). With
    ``select_bias`` ``[experts]`` (DeepSeek-V3's ``noaux_tc``) the ``top_k``
    are taken by ``s + select_bias`` and weighted by their unbiased ``s``:
    the bias steers the load and never scales an expert's output, and no
    gradient reaches it. ``reads`` (``own`` or ``early``) and ``act`` name
    the tensor ``x`` is and the experts' activation in the count alone.
    -> (expert ids ``[N, k]`` int32, weights ``[N, k]`` float32)."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"unknown router scoring {scoring!r}")
    _ROUTE.inc(select="plain" if select_bias is None else "biased",
               scoring=scoring, input=reads, act=act)
    with jax.named_scope("moe.route"):
        s = jnp.dot(x, w_router, preferred_element_type=jnp.float32)
        s = jax.nn.sigmoid(s) if scoring == "sigmoid" \
            else jax.nn.softmax(s, axis=-1)
        if select_bias is None:
            top_s, top_e = jax.lax.top_k(s, top_k)
            w = scale * top_s / jnp.sum(top_s, axis=-1, keepdims=True)
        else:
            _, top_e = jax.lax.top_k(s + select_bias, top_k)
            top_s = jnp.take_along_axis(s, top_e, axis=-1)
            w = scale * top_s / (jnp.sum(top_s, axis=-1, keepdims=True)
                                 + 1e-20)
        return top_e.astype(jnp.int32), w


def held_share(top_e, w, first: int, count: int):
    """The weights of each token's chosen experts renormalised over those of
    them held here (``first`` to ``first + count``), the others at nought:
    the held experts a token chose stand in for its chosen experts held
    elsewhere. A token that chose none of them keeps nought everywhere. Its
    output no longer depends on the router's scores for absent experts, so
    no gradient pushes tokens towards the held experts or away from them."""
    here = (top_e >= first) & (top_e < first + count)
    w = jnp.where(here, w, 0.0)
    total = jnp.sum(w, axis=-1, keepdims=True)
    return w / jnp.where(total > 0, total, 1.0)


def plan(top_e, first: int, count: int):
    """Sort the assignments to the ``count`` experts from ``first`` by
    expert. -> (order ``[N * k]``: assignment ids, held experts' first;
    ends ``[count]``: where each held expert's rows end in that order;
    tokens ``[count]``: rows per held expert)."""
    local = top_e.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    tokens = jnp.zeros((count + 1,), jnp.int32).at[local].add(1)[:count]
    return order, jnp.cumsum(tokens), tokens


def token_major(order, ends, chunk_rows: int):
    """The held assignments chunk by chunk of ``order``, each chunk's in
    ascending assignment id, so that a token's rows in a chunk lie side by
    side. -> (``tm`` ``[N * k]``: assignment ids, chunk 0's first, the
    assignments held nowhere here last; ``src`` ``[N * k]``: each one's row
    in ``order``). Chunk ``c``'s are ``tm[c * chunk_rows:(c + 1) *
    chunk_rows]`` up to ``ends[-1]``: a chunk but the last holds exactly
    ``chunk_rows``."""
    n = order.shape[0]
    pos = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    chunk = jnp.where(pos < ends[-1], pos // chunk_rows, n)
    tm = jnp.argsort(chunk, stable=True).astype(jnp.int32)
    return tm, jnp.take(pos, tm)


def segment_tiling(n_tokens: int, rows: int, d: int, itemsize: int,
                   per_token: int):
    """The kernel's (token block, row block, steps a token block) for
    ``rows`` token-major rows of width ``d`` onto ``n_tokens``, a token
    holding at most ``per_token`` of them; or why it has none (``shape``,
    ``vmem``). A token block's rows span at most ``steps`` row blocks, each
    fetched whole, so a row block about the size of a token block's rows
    fetches few rows that it does not sum: 128 rows ran the combine fastest
    on a v5e at every expert cell's shape, against 256 and 512 (PERF.md,
    section 6)."""
    if itemsize not in (2, 4):
        return "shape"
    bt = _TOKEN_BLOCK if n_tokens % _TOKEN_BLOCK == 0 else n_tokens
    if bt != _TOKEN_BLOCK and (n_tokens > 8 * _TOKEN_BLOCK or n_tokens % 8):
        return "shape"
    sub = 128 if rows % 128 == 0 else rows
    if sub == rows and (rows > 2048 or rows % 16):
        return "shape"
    # rows and tokens double-buffered, the accumulated block, the products
    if 2 * sub * d * itemsize + 3 * bt * d * 4 > _fa._SCOPED_VMEM * 3 // 4:
        return "vmem"
    steps = min(rows // sub, -(-bt * per_token // sub) + 1)
    return bt, sub, steps


def _weighted_rows(hit, w, rows):
    """``sum_j [hit[t, j]] w[j] rows[j]`` -> ``[bt, d]`` float32 on the MXU.
    The one-hot is exact in bfloat16; a weight is cut into three bfloat16
    parts (24 bits of mantissa together), so each product with a bfloat16
    row is exact and only the sums round, in float32."""
    f32 = jnp.float32
    if rows.dtype == f32:
        p = hit.astype(f32) if w is None else jnp.where(hit, w, 0.0)
        return jnp.dot(p, rows, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=f32)
    if w is None:
        return jnp.dot(hit.astype(rows.dtype), rows, preferred_element_type=f32)
    p = jnp.where(hit, w, 0.0)
    out = None
    for _ in range(3):
        part = p.astype(rows.dtype)
        step = jnp.dot(part, rows, preferred_element_type=f32)
        out = step if out is None else out + step
        p = p - part.astype(f32)
    return out


def _segment_kernel(lo_ref, hi_ref, carry_ref, tok_ref, *refs, bt, weighted):
    """Grid (token blocks, row blocks of one): the token block's float32 sum
    stays in its output block across the row blocks and is written once."""
    pl, pltpu = _fa._load_pallas()
    w_ref, rows_ref, acc_ref, out_ref, sem = (
        refs if weighted else (None,) + refs)
    b, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s == 0)
    def _():
        @pl.when(carry_ref[0] > 0)
        def _():
            copy = pltpu.make_async_copy(acc_ref.at[pl.ds(b * bt, bt)],
                                         out_ref, sem)
            copy.start()
            copy.wait()

        @pl.when(carry_ref[0] == 0)
        def _():
            out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(lo_ref[b] + s <= hi_ref[b])
    def _():
        hit = (jax.lax.broadcasted_iota(jnp.int32, (bt, tok_ref.shape[1]), 0)
               == tok_ref[...] - b * bt)
        out_ref[...] += _weighted_rows(
            hit, None if w_ref is None else w_ref[...], rows_ref[...])


def _segment_call(rows, tok, w, acc, carry, tiling, interpret):
    pl, pltpu = _fa._load_pallas()
    n, d = acc.shape
    bt, sub, steps = tiling
    blocks = rows.shape[0] // sub
    # each token block's rows, [start, end), as row blocks [lo, hi]
    edge = jnp.searchsorted(tok, jnp.arange(0, n + 1, bt, dtype=tok.dtype))
    lo = jnp.minimum(edge[:-1] // sub, blocks - 1).astype(jnp.int32)
    hi = jnp.where(edge[1:] > edge[:-1], (edge[1:] - 1) // sub,
                   lo - 1).astype(jnp.int32)

    def row_block(b, s, lo, hi, carry):
        return jnp.minimum(lo[b] + s, jnp.maximum(hi[b], lo[b]))

    line = pl.BlockSpec((1, sub), lambda b, s, *p: (0, row_block(b, s, *p)))
    weighted = w is not None
    ins = [tok.reshape(1, -1)] + ([w.reshape(1, -1)] if weighted else []) \
        + [rows, acc]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // bt, steps),
        in_specs=[line] * (1 + weighted)
        + [pl.BlockSpec((sub, d), lambda b, s, *p: (row_block(b, s, *p), 0)),
           pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((bt, d), lambda b, s, *p: (b, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())])
    return pl.pallas_call(
        functools.partial(_segment_kernel, bt=bt, weighted=weighted),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        input_output_aliases={3 + len(ins) - 1: 0},
        compiler_params=_fa._compiler_params(pltpu, ("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_combine",
    )(lo, hi, jnp.reshape(carry, (1,)).astype(jnp.int32), *ins)


def _segment_jnp(rows, tok, w, acc, per_token):
    """The same sums in ``jnp``: each token's ``j``-th row, for ``j`` below
    ``per_token``, gathered and added where the token has one."""
    n = acc.shape[0]
    edge = jnp.searchsorted(tok, jnp.arange(n + 1, dtype=tok.dtype))
    start, count = edge[:-1], edge[1:] - edge[:-1]
    out = acc
    for j in range(per_token):
        at = jnp.minimum(start + j, rows.shape[0] - 1)
        r = jnp.take(rows, at, axis=0).astype(jnp.float32)
        if w is not None:
            r = r * jnp.take(w, at)[:, None]
        out = out + jnp.where((j < count)[:, None], r, 0.0)
    return out


def segment_sum(rows, tok, w, acc, carry, per_token: int, tiling=None):
    """``acc`` plus, for each token ``t``, the sum over the rows ``j`` with
    ``tok[j] == t`` of ``w[j] * rows[j]``, in float32. ``rows`` ``[C, d]``
    token-major: ``tok`` ``[C]`` ascending, the token count past the last row
    that counts (such rows must be finite); ``w`` ``[C]`` float32 or None (a
    weight of 1); a token holds at most ``per_token`` rows; ``acc`` ``[N,
    d]`` float32, zeros where ``carry`` is false (the kernel then does not
    read it). ``tiling`` (from :func:`segment_tiling`): the ``moe_combine``
    kernel, else the ``jnp`` form."""
    if tiling is None:
        return _segment_jnp(rows, tok, w, acc, per_token)
    return _segment_call(rows, tok, w, acc, carry, tiling, _fa._interpret())


def _gated(x, w1, w3, w2, sizes, keep, activation):
    """The experts' gated feed-forward over rows sorted by expert,
    ``(act(x W1) * (x W3)) W2`` with ``activation`` by name. A row
    past the last group is no expert's and the grouped product leaves it
    unwritten: every product's result is masked there (``keep``), forward
    and, through the mask's transpose, backward, so that nothing undefined
    ever meets a multiplication (a stale NaN times a zero cotangent is a
    NaN in the router's gradient, and the sentinel skips the step: two of
    the first eight seeds on the chip, PERF.md, PR 31)."""
    def rd(a, b):
        return jnp.where(keep, jax.lax.ragged_dot(a, b, sizes), 0)

    return rd(_act.get(activation)(rd(x, w1)) * rd(x, w3), w2)


def _sizes(c, ends, chunk_rows):
    """Rows of each held expert inside chunk ``c`` of the sorted order."""
    lo = c * chunk_rows
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    return (jnp.clip(ends, lo, lo + chunk_rows)
            - jnp.clip(starts, lo, lo + chunk_rows))


def _rows(c, x, order, ends, chunk_rows, top_k):
    """Rows ``[c * chunk_rows, (c + 1) * chunk_rows)`` of the sorted
    assignments. -> (assignment ids, rows that carry one ``[C, 1]``, their
    tokens' inputs, zero past the last, ``[C, d]``)."""
    rows = c * chunk_rows + jnp.arange(chunk_rows, dtype=jnp.int32)
    a = jnp.take(order, jnp.minimum(rows, order.shape[0] - 1))
    keep = (rows < ends[-1])[:, None]
    return a, keep, jnp.where(keep, jnp.take(x, a // top_k, axis=0), 0)


def _to_tokens(c, rows, tm, src, ends, chunk_rows, top_k, n_tokens):
    """Chunk ``c``'s sorted ``rows`` token-major. -> (the rows, zero past
    the last held one; their assignment ids; their tokens, ``n_tokens`` past
    the last)."""
    lo = c * chunk_rows
    j = lo + jnp.arange(chunk_rows, dtype=jnp.int32)
    held = j < ends[-1]
    j = jnp.minimum(j, tm.shape[0] - 1)
    a = jnp.take(tm, j)
    at = jnp.clip(jnp.take(src, j) - lo, 0, chunk_rows - 1)
    return (jnp.where(held[:, None], jnp.take(rows, at, axis=0), 0), a,
            jnp.where(held, a // top_k, n_tokens))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def held_experts(x, w, w1, w3, w2, order, ends, chunk_rows, top_k,
                 activation="swish"):
    """Sum over the held experts ``e`` chosen by each token of ``w_e
    FFN_e(x)``. ``x`` ``[N, d]``; ``w`` ``[N, k]`` float32; ``w1`` / ``w3``
    ``[count, d, f]``, ``w2`` ``[count, f, d]``; ``order`` / ``ends`` from
    :func:`plan`; ``activation`` the gate's, by name. -> (``[N, d]``
    float32, rows computed)."""
    return _held_fwd(x, w, w1, w3, w2, order, ends, chunk_rows, top_k,
                     activation)[0]


def _n_chunks(ends, chunk_rows):
    return (ends[-1] + chunk_rows - 1) // chunk_rows


def _combine(x, chunk_rows, per_token, direction):
    """The segment sum's tiling for this site (None: its ``jnp`` form),
    counted with the reason where there is none."""
    if _partitioned() is not None:
        why = "gspmd"
    elif _fa.mode() == "off":
        why = "mode"
    elif _fa.mode() != "force" and not _tpu_available():
        why = "platform"
    else:
        why = segment_tiling(x.shape[0], chunk_rows, x.shape[1],
                             x.dtype.itemsize, per_token)
    if isinstance(why, str):
        _COMBINE.inc(**{"pass": direction}, decision="jnp", why=why)
        return None
    _COMBINE.inc(**{"pass": direction}, decision="kernel")
    return why


def _held_fwd(x, w, w1, w3, w2, order, ends, chunk_rows, top_k, activation):
    n, per_token = x.shape[0], min(top_k, w1.shape[0])
    tiling = _combine(x, chunk_rows, per_token, "forward")
    tm, src = token_major(order, ends, chunk_rows)

    def body(c, carry):
        out, done = carry
        a, keep, xg = _rows(c, x, order, ends, chunk_rows, top_k)
        y = _gated(xg, w1, w3, w2, _sizes(c, ends, chunk_rows), keep,
                   activation)
        y, at, tok = _to_tokens(c, y, tm, src, ends, chunk_rows, top_k, n)
        wt = jnp.where(tok < n, jnp.take(w.reshape(-1), at), 0.0)
        out = segment_sum(y, tok, wt, out, c > 0, per_token, tiling)
        return out, done + jnp.sum(keep)

    out, done = jax.lax.fori_loop(
        0, _n_chunks(ends, chunk_rows), body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros((), jnp.int32)))
    return (out, done), (x, w, w1, w3, w2, order, ends, tm, src)


def _held_bwd(chunk_rows, top_k, activation, res, cts):
    x, w, w1, w3, w2, order, ends, tm, src = res
    g = cts[0]
    n, per_token = x.shape[0], min(top_k, w1.shape[0])
    tiling = _combine(x, chunk_rows, per_token, "backward")

    def body(c, acc):
        a, keep, xg = _rows(c, x, order, ends, chunk_rows, top_k)
        sizes = _sizes(c, ends, chunk_rows)
        g_rows = jnp.take(g, a // top_k, axis=0)

        def contribution(xg, w, w1, w3, w2):
            y = _gated(xg, w1, w3, w2, sizes, keep, activation)
            wt = jnp.where(keep, jnp.take(w.reshape(-1), a)[:, None], 0.0)
            return jnp.sum(y.astype(jnp.float32) * wt * g_rows)

        g_xg, gw, *g_experts = jax.grad(contribution, argnums=(0, 1, 2, 3, 4))(
            xg, w, w1, w3, w2)
        # rows past the last group carry no input: what the grouped
        # product's transpose leaves there is no gradient
        g_xg, _, tok = _to_tokens(c, jnp.where(keep, g_xg, 0), tm, src, ends,
                                  chunk_rows, top_k, n)
        gx = segment_sum(g_xg, tok, None, acc[0], c > 0, per_token, tiling)
        # an expert with no row in this chunk (the rule once a second chunk
        # is walked) has no gradient from it, whatever the grouped product
        # leaves in an empty group's slot
        has_rows = (sizes > 0)[:, None, None]
        g_experts = [jnp.where(has_rows, ge, 0) for ge in g_experts]
        return (gx,) + jax.tree.map(lambda a, b: a + b.astype(a.dtype),
                                    acc[1:], (gw, *g_experts))

    zeros = tuple(jnp.zeros(a.shape, jnp.float32) for a in (x, w, w1, w3, w2))
    acc = jax.lax.fori_loop(0, _n_chunks(ends, chunk_rows), body, zeros)
    grads = tuple(a.astype(p.dtype) for a, p in zip(acc, (x, w, w1, w3, w2)))
    return grads + (None, None)


held_experts.defvjp(_held_fwd, _held_bwd)
