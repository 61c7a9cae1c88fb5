"""Sparse-expert routing and the grouped products of the experts held here.

The router scores every token against all ``num_experts`` and keeps its
``top_k``; the layer is told which experts it holds (``first``, ``count``)
and computes their part of the result for the tokens routed to them. No
capacity, no dropped token: the assignments to held experts are sorted by
expert and walked in chunks of ``chunk_rows`` rows, as many chunks as the
routing needs (a ``while`` with a trip count read from the routing), so a
routing that sends every token to one expert costs more chunks and loses
nothing. Inside a chunk the experts run as grouped matrix products
(``jax.lax.ragged_dot``: rows sorted by expert, one group per expert).
What the experts held elsewhere would add is left out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..runtime import telemetry as _tel

_ROUTE = _tel.counter(
    "moe.route", "router sites by what the top-k is taken on, the scores "
    "(plain) or the scores plus a selection bias (biased), and by how the "
    "router's outputs become scores (sigmoid, softmax), once a traced site")


def route(x, w_router, top_k: int, scale: float, select_bias=None,
          scoring: str = "sigmoid"):
    """Scores in float32 over all experts, the ``top_k`` largest per token,
    weights ``scale * s / sum(s)`` over the chosen. ``scoring``: ``sigmoid``
    of each router output, or ``softmax`` over all of them (the weights are
    then the probabilities renormalised over the chosen). With
    ``select_bias`` ``[experts]`` (DeepSeek-V3's ``noaux_tc``) the ``top_k``
    are taken by ``s + select_bias`` and weighted by their unbiased ``s``:
    the bias steers the load and never scales an expert's output, and no
    gradient reaches it.
    -> (expert ids ``[N, k]`` int32, weights ``[N, k]`` float32)."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"unknown router scoring {scoring!r}")
    _ROUTE.inc(select="plain" if select_bias is None else "biased",
               scoring=scoring)
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


def _gated(x, w1, w3, w2, sizes, keep):
    """The experts' gated feed-forward over rows sorted by expert. A row
    past the last group is no expert's and the grouped product leaves it
    unwritten: every product's result is masked there (``keep``), forward
    and, through the mask's transpose, backward, so that nothing undefined
    ever meets a multiplication (a stale NaN times a zero cotangent is a
    NaN in the router's gradient, and the sentinel skips the step: two of
    the first eight seeds on the chip, PERF.md, PR 31)."""
    def rd(a, b):
        return jnp.where(keep, jax.lax.ragged_dot(a, b, sizes), 0)

    return rd(jax.nn.silu(rd(x, w1)) * rd(x, w3), w2)


def _sizes(c, ends, chunk_rows):
    """Rows of each held expert inside chunk ``c`` of the sorted order."""
    lo = c * chunk_rows
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    return (jnp.clip(ends, lo, lo + chunk_rows)
            - jnp.clip(starts, lo, lo + chunk_rows))


def _chunk(c, x, w, w1, w3, w2, order, ends, chunk_rows, top_k):
    """Rows ``[c * chunk_rows, (c + 1) * chunk_rows)`` of the sorted
    assignments: gather their tokens, run their experts, weight the results.
    -> (token of each row, weighted rows ``[chunk_rows, d]`` float32, rows
    that carried an assignment)."""
    lo = c * chunk_rows
    rows = lo + jnp.arange(chunk_rows, dtype=jnp.int32)
    live = rows < ends[-1]
    a = jnp.take(order, jnp.minimum(rows, order.shape[0] - 1))
    tok = a // top_k
    sizes = _sizes(c, ends, chunk_rows)
    keep = live[:, None]
    y = _gated(jnp.where(keep, jnp.take(x, tok, axis=0), 0), w1, w3, w2,
               sizes, keep)
    wt = jnp.where(live, jnp.take(w.reshape(-1), a), 0.0)[:, None]
    return tok, y.astype(jnp.float32) * wt, jnp.sum(live)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def held_experts(x, w, w1, w3, w2, order, ends, chunk_rows, top_k):
    """Sum over the held experts ``e`` chosen by each token of ``w_e
    FFN_e(x)``. ``x`` ``[N, d]``; ``w`` ``[N, k]`` float32; ``w1`` / ``w3``
    ``[count, d, f]``, ``w2`` ``[count, f, d]``; ``order`` / ``ends`` from
    :func:`plan`. -> (``[N, d]`` float32, rows computed)."""
    return _held_fwd(x, w, w1, w3, w2, order, ends, chunk_rows, top_k)[0]


def _n_chunks(ends, chunk_rows):
    return (ends[-1] + chunk_rows - 1) // chunk_rows


def _held_fwd(x, w, w1, w3, w2, order, ends, chunk_rows, top_k):
    def body(c, carry):
        out, done = carry
        tok, y, n = _chunk(c, x, w, w1, w3, w2, order, ends, chunk_rows,
                           top_k)
        return out.at[tok].add(y), done + n

    out, done = jax.lax.fori_loop(
        0, _n_chunks(ends, chunk_rows), body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros((), jnp.int32)))
    return (out, done), (x, w, w1, w3, w2, order, ends)


def _held_bwd(chunk_rows, top_k, res, cts):
    x, w, w1, w3, w2, order, ends = res
    g = cts[0]

    def body(c, acc):
        def contribution(x, w, w1, w3, w2):
            tok, y, _ = _chunk(c, x, w, w1, w3, w2, order, ends, chunk_rows,
                               top_k)
            return jnp.sum(y * jnp.take(g, tok, axis=0))

        gx, gw, *g_experts = jax.grad(contribution, argnums=(0, 1, 2, 3, 4))(
            x, w, w1, w3, w2)
        # an expert with no row in this chunk (the rule once a second chunk
        # is walked) has no gradient from it, whatever the grouped product
        # leaves in an empty group's slot
        has_rows = (_sizes(c, ends, chunk_rows) > 0)[:, None, None]
        g_experts = [jnp.where(has_rows, ge, 0) for ge in g_experts]
        return jax.tree.map(lambda a, b: a + b.astype(a.dtype), acc,
                            (gx, gw, *g_experts))

    zeros = tuple(jnp.zeros(a.shape, jnp.float32) for a in (x, w, w1, w3, w2))
    acc = jax.lax.fori_loop(0, _n_chunks(ends, chunk_rows), body, zeros)
    grads = tuple(a.astype(p.dtype) for a, p in zip(acc, (x, w, w1, w3, w2)))
    return grads + (None, None)


held_experts.defvjp(_held_fwd, _held_bwd)
