"""The weighted next-token cross-entropy of a language-model head, a block of
positions at a time, with the head's gradients formed in the same pass.

A head's loss is ``sum w(i) ce(i)`` over its positions, with ``ce(i) =
logsumexp(h(i) W) - (h(i) W)[next(i)]`` and row weights ``w`` that are known
before any logits are made (``1 / N``, or an exit probability over ``N``).
The loss is linear in ``ce``, so everything the backward pass needs of a
block can be made while that block's float32 logits exist: ``w (softmax -
onehot)``, its product back to the hidden states and the block's term of
``W``'s gradient. :func:`weighted_cross_entropy` is a ``jax.custom_vjp``
whose forward rule does that, one visit a block with ``W``'s gradient as the
carry, and whose backward rule only scales what the forward rule kept by the
loss's cotangent. Where nothing is differentiated the blocks make their
logits and logsumexp and nothing else.

Inside a recomputed segment (``nn/memory.py`` ``checkpoint``) the three
residuals are tagged ``memory.KEPT``, so that the segment's recomputation
holds nothing of the head. ``lm_head.gradients`` counts what a differentiated
site did. A ``custom_vjp`` function has no forward-mode derivative: ``jax.jvp``
through a training head raises.

Logits, logsumexp and ``softmax - onehot`` are float32 whatever ``h`` and
``W`` are; the two products that carry the loss back take the float32
cotangent beside the operand as it arrives, as JAX's transpose of the logits
product does, and ``W``'s gradient is summed over the blocks in ``W``'s dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..runtime import telemetry as _tel

_GRADIENTS = _tel.counter(
    "lm_head.gradients",
    "differentiated language-model heads by layer and where their gradients "
    "are formed: in the forward pass, and kept across a recomputed segment "
    "or not, once a traced site")


def _memory():
    from ..nn import memory                     # nn imports ops, not back
    return memory


def _block(hb, W, yb):
    """One block: -> float32 logits ``[C, V]``, their logsumexp ``[C]`` and
    the cross-entropy against ``yb`` ``[C]``."""
    logits = jnp.dot(hb, W, preferred_element_type=jnp.float32)
    picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
    lse = jax.nn.logsumexp(logits, axis=-1)
    return logits, lse, lse - picked


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _weighted_ce(h, W, nxt, w, layer, kept):
    ce = jax.lax.map(lambda a: _block(a[0], W, a[1])[2], (h, nxt))
    return jnp.sum(w * ce), ce


def _weighted_ce_fwd(h, W, nxt, w, layer, kept):
    _GRADIENTS.inc(layer=layer,
                   decision="in_forward_kept" if kept else "in_forward")
    rows, cols = ((0,), (0,)), ((1,), (1,))

    def visit(dW, block):
        hb, yb, wb = block
        logits, lse, ce = _block(hb, W, yb)
        hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) \
            == yb[:, None]
        dlogits = wb[:, None] * (jnp.exp(logits - lse[:, None])
                                 - hit.astype(jnp.float32))
        dhb = jax.lax.dot_general(dlogits, W, (cols, ((), ())),
                                  preferred_element_type=jnp.float32)
        dWb = jax.lax.dot_general(hb, dlogits, (rows, ((), ())),
                                  preferred_element_type=jnp.float32)
        return dW + dWb.astype(W.dtype), (ce, dhb.astype(h.dtype))

    dW, (ce, dh) = jax.lax.scan(visit, jnp.zeros_like(W), (h, nxt, w))
    if kept:
        name = _memory().KEPT
        dh, dW, ce = (checkpoint_name(a, name) for a in (dh, dW, ce))
    return (jnp.sum(w * ce), ce), (dh, dW, ce)


def _weighted_ce_bwd(layer, kept, residuals, cotangents):
    dh, dW, ce = residuals
    g = cotangents[0]            # ``ce`` is handed out under stop_gradient

    def scaled(a):
        return (g * a.astype(jnp.float32)).astype(a.dtype)
    return scaled(dh), scaled(dW), None, g * ce


_weighted_ce.defvjp(_weighted_ce_fwd, _weighted_ce_bwd)


def weighted_cross_entropy(h, W, nxt, w, *, layer: str):
    """``h`` ``[n, C, d]`` hidden states in ``n`` blocks of ``C`` positions,
    ``W`` ``[d, V]``, ``nxt`` ``[n, C]`` the token each position is scored
    against, ``w`` ``[n, C]`` float32 row weights -> the scalar ``sum w *
    ce`` (float32) and ``ce`` ``[n, C]`` (float32, under ``stop_gradient``:
    for counters and tests).

    Only the scalar is differentiable, with respect to ``h``, ``W`` and
    ``w``: a per-position cotangent would not factor out of ``W``'s gradient,
    which is summed over the positions in the forward pass. One block's
    logits are all that is ever held. ``layer`` labels the counter."""
    kept = _memory().recomputing()
    total, ce = _weighted_ce(h, W, jnp.asarray(nxt, jnp.int32),
                             jnp.asarray(w, jnp.float32), layer, kept)
    return total, jax.lax.stop_gradient(ce)
