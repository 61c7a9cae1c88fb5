"""The timed path broken underneath, for ``test_faults.py``: each function
takes a built entry and replaces the program's compiled step with one that
commits one fault, so that the rest of the run (first steps, window,
reference, comparison, result line) is the harness's own."""

import jax
import jax.numpy as jnp


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def epoch_state_unchanged(entry):
    """``fit_on_device``: the launch computes its losses and hands back the
    state it was given."""
    net = entry.net
    real = net._build_epoch_fn()

    def broken(params, opt, bn, sentinel, start, key, xs, ys):
        *_, losses = real(_copy(params), _copy(opt), _copy(bn),
                          _copy(sentinel), start, key, xs, ys)
        return params, opt, bn, sentinel, losses

    net._epoch_fn = broken
    return entry


def epoch_half_batch(entry):
    """``fit_on_device``: the second half of every batch is left out and the
    mean taken over the rest."""
    net = entry.net
    real = net._build_epoch_fn()

    def broken(params, opt, bn, sentinel, start, key, xs, ys):
        half = xs[0].shape[1] // 2
        return real(params, opt, bn, sentinel, start, key,
                    tuple(x[:, :half] for x in xs),
                    tuple(y[:, :half] for y in ys))

    net._epoch_fn = broken
    return entry


def samediff_state_unchanged(entry):
    """``SameDiff.fit``: the step computes its loss and hands back the
    weights it was given."""
    sd = entry.sd
    real = sd._fit_step_cached()

    def broken(carry, opt_state, other_vals, step_i, feeds, sentinel):
        _, _, _, loss = real(_copy(carry), _copy(opt_state), other_vals,
                             step_i, feeds, _copy(sentinel))
        return carry, opt_state, sentinel, loss

    sd._fit_step_cached = lambda: broken
    return entry


def samediff_answer_altered(entry):
    """``SameDiff.fit``: the update is applied twice over (every leaf moves
    double)."""
    sd = entry.sd
    real = sd._fit_step_cached()

    def broken(carry, opt_state, other_vals, step_i, feeds, sentinel):
        before = _copy(carry)
        new, opt_state, sentinel, loss = real(carry, opt_state, other_vals,
                                              step_i, feeds, sentinel)
        new = jax.tree.map(lambda n, b: n + (n - b).astype(n.dtype),
                           new, before)
        return new, opt_state, sentinel, loss

    sd._fit_step_cached = lambda: broken
    return entry


def wrapper_no_exchange(entry):
    """``ParallelWrapper.fit``: only the first chip's rows reach the update,
    as if the gradients were never exchanged and chip 0's copy were read."""
    pw = entry.pw
    real_build = pw._build

    def build():
        step_fn, shard_args = real_build()
        n = pw.mesh.devices.size

        def broken(*args):
            rows = jax.tree.leaves(args[5])[0].shape[0]

            def first_shard(a):
                if getattr(a, "ndim", 0) and a.shape[0] == rows:
                    return a[: rows // n]
                return a

            # (params, state, bn, step, key, x, y, masks.., sentinel)
            return step_fn(*args[:5], *jax.tree.map(first_shard, args[5:9]),
                           *args[9:])

        return broken, shard_args

    pw._build = build
    return entry
