"""Gradient micro-accumulation: one optimizer step over k microbatches.

Shared by both engines' pure-step factory (``nn/engine.py``
``NetworkEngine._build_train_step``): the incoming
batch [B, ...] is reshaped to [k, B/k, ...] and a ``lax.scan`` accumulates
the gradient before the SINGLE updater application — global batch can
grow past HBM (only one microbatch of activations is live at a time) without
touching user code, and under data parallelism the accumulation amortizes
the per-step parameter all-gather/grad reduce exactly as the cross-replica
sharded-weight-update paper prescribes (Xu et al. 2020, PAPERS.md).

Exactness contract: losses are means over the (unmasked) batch, so the
accumulator combines microbatches as a WEIGHTED mean — each microbatch's
loss/gradient is weighted by its unmasked label count (via the engine's
``weight_fn``; equal weights when there is no label mask). With that
weighting, ``accum_steps=k`` at microbatch B/k matches one step at batch B
to float tolerance even when masked/padded rows are distributed unevenly
across microbatches (e.g. the DP pad path, where a ragged tail can leave
entire microbatches fully padded — weight 0, exactly as if they were never
seen; a plain mean would silently down-scale the gradient by the number of
real-data-free microbatches). Tested in tests/test_shard_update.py.

Recorded divergences (approximate, not exact):

- **batch-global losses**: the weighted-mean recombination is exact only
  for losses that are (masked) MEANS over examples. A loss computed from
  batch-global statistics — ``fmeasure`` (F-beta over whole-batch
  tp/fp/fn sums) is the one in the catalog — is not mean-decomposable:
  under ``accum_steps=k`` it is evaluated per microbatch and averaged,
  which optimizes a (close but) different objective than the full-batch
  loss, with no error raised. Use ``accum_steps=1`` when the exact
  batch-global objective matters.
- **propagated feature masks**: the loss intersects the explicit label
  mask with the network-propagated mask (ops/losses.combine_masks); the
  weight only sees the label mask, so counts that differ through the
  propagated component make the weighting proportional, not exact.
- **multi-output graphs with differing per-output masks**: one scalar
  weight per microbatch (the combined count over all outputs, see
  ``multi_output_weight``) cannot match every output's own normalization
  count when the per-output counts are non-proportional; no output's real
  rows are ever zero-dropped, but their relative weighting is approximate.
- **train-mode BatchNorm**: batch moments are per-microbatch (B/k), not
  full-batch — same as running k real steps at B/k; running stats thread
  sequentially through the scan.
- **stochastic layers**: each microbatch draws its own dropout key
  (``fold_in(key, i)``), so the noise pattern differs from a single
  full-batch draw (necessarily — shapes differ).

The regularization term is added inside every microbatch loss; because the
accumulator takes a (weighted) MEAN over microbatches, both the reported
loss and the gradient count the penalty exactly once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def split_microbatches(batch, k: int):
    """Reshape every array leaf [B, ...] -> [k, B/k, ...]; ``None`` leaves
    (absent masks) pass through as pytree-empty nodes. Raises when the
    batch dimension is not divisible by ``k`` (a silent drop or pad here
    would corrupt the weighted mean)."""
    def split(a):
        b = a.shape[0]
        if b % k:
            raise ValueError(
                f"batch size {b} is not divisible by accum_steps={k}")
        return a.reshape((k, b // k) + a.shape[1:])
    return jax.tree.map(split, batch)


def accumulate_gradients(value_and_grad_fn, params, bn_state, key, k: int,
                         batch, weight_fn=None):
    """Scan ``value_and_grad_fn(params, bn_state, key_i, *microbatch)`` over
    ``k`` microbatches, returning ``((loss, final_bn_state), grads)`` — the
    same contract as one call of the fn on the full batch, with peak
    activation memory of a single microbatch.

    ``weight_fn(*microbatch) -> scalar`` supplies each microbatch's weight
    (its unmasked label count); ``None`` means equal weights (the exact
    choice for unmasked batches). Losses and gradients combine as the
    weighted mean; an all-masked microbatch (weight 0) contributes nothing.
    """
    micro = split_microbatches(batch, k)

    def _acc_zero(a):
        # gradient accumulation always carries full mantissa: a 16-bit
        # params tree (the engines' hoisted mixed-precision path casts
        # masters to the compute dtype BEFORE the scan) still accumulates
        # its per-microbatch grads into an f32 accumulator — the bf16/f16
        # grads promote exactly on add, reproducing what the per-microbatch
        # cast-backward produced when the cast lived inside the scan
        if a.dtype in (jnp.bfloat16, jnp.float16):
            return jnp.zeros(a.shape, jnp.float32)
        return jnp.zeros_like(a)

    zeros = jax.tree.map(_acc_zero, params)

    def body(carry, xs):
        g_acc, l_acc, w_acc, bn = carry
        i, mb = xs
        (loss, bn), g = value_and_grad_fn(
            params, bn, jax.random.fold_in(key, i), *mb)
        # weight_fn may return None (no label mask — static across the
        # whole batch, so this branch is trace-consistent): equal weights
        w_val = None if weight_fn is None else weight_fn(*mb)
        w = jnp.float32(1.0) if w_val is None else \
            jnp.asarray(w_val, jnp.float32)
        g_acc = jax.tree.map(lambda a, b: a + w * b, g_acc, g)
        return (g_acc, l_acc + w * loss, w_acc + w, bn), loss

    (g_sum, l_sum, w_tot, new_bn), _ = jax.lax.scan(
        body, (zeros, jnp.float32(0.0), jnp.float32(0.0), bn_state),
        (jnp.arange(k), micro))
    # all-masked full batch: weight 0 everywhere -> zero loss/grads, not NaN
    w_tot = jnp.maximum(w_tot, 1e-8)
    grads = jax.tree.map(lambda g: g / w_tot, g_sum)
    return (l_sum / w_tot, new_bn), grads


def label_count_weight(lm):
    """The standard microbatch weight: unmasked label count, or ``None``
    (equal weights) when there is no label mask. The engines call this with
    their own batch layout's label-mask slot."""
    if lm is None:
        return None
    return jnp.sum(jnp.asarray(lm, jnp.float32))


def multi_output_weight(xs, ys, fms, lms):
    """Graph-engine microbatch weight: the combined unmasked count over ALL
    outputs, with an unmasked output counting every example. One scalar
    weight cannot match every output's own normalization when per-output
    counts are non-proportional (recorded divergence above), but summing
    over outputs guarantees a microbatch holding real data in ANY output
    keeps nonzero weight — taking only one output's count could zero-drop
    another output's genuine rows. Exact for the DP pad path (every output
    shares the synthesized pad mask, so counts are proportional) and for a
    fully-masked output alongside unmasked ones (counts stay equal)."""
    if all(lm is None for lm in lms):
        return None
    total = jnp.float32(0.0)
    for y, lm in zip(ys, lms):
        if lm is None:
            total = total + jnp.float32(y.shape[0])
        else:
            total = total + jnp.sum(jnp.asarray(lm, jnp.float32))
    return total
