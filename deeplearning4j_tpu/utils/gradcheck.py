"""Finite-difference gradient checking.

TPU-native equivalent of DL4J's central correctness tool (reference:
``deeplearning4j .../gradientcheck/GradientCheckUtil.java``†,
``nd4j-api .../autodiff/validation/GradCheckUtil.java``† per SURVEY.md §4;
reference mount was empty, citations upstream-relative, unverified).

Like the reference, checks run in float64 on CPU (TPU is bf16/fp32-centric;
fp64 FD would be noise-limited on device). ``check_gradients`` works on any
(pytree-of-arrays -> scalar) function, so it covers raw ops, layers, and whole
models; the per-parameter relative-error criterion matches GradientCheckUtil
(maxRelError with an absolute-error floor).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def check_gradients(fn, params, eps=1e-5, max_rel_error=1e-5, min_abs_error=1e-8,
                    verbose=False):
    """Compare analytic ``jax.grad(fn)`` against central finite differences.

    fn: pytree -> scalar, pure. params: pytree of float arrays. Runs on CPU in
    float64 regardless of the default device/dtype. Returns (ok, max_rel_err,
    failures) where failures is a list of (path, index, analytic, numeric).
    """
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        with jax.enable_x64(True):
            p64 = jax.tree.map(lambda a: jnp.asarray(np.asarray(a), dtype=jnp.float64), params)
            analytic = jax.grad(fn)(p64)
            leaves, treedef = jax.tree.flatten(p64)
            an_leaves = jax.tree.leaves(analytic)
            paths = [str(p) for p, _ in jax.tree_util.tree_flatten_with_path(p64)[0]]

            failures = []
            worst = 0.0
            for li, (leaf, an, path) in enumerate(zip(leaves, an_leaves, paths)):
                flat = np.array(leaf, dtype=np.float64).ravel()
                an_flat = np.asarray(an).ravel()
                for i in range(flat.size):
                    orig = flat[i]
                    flat[i] = orig + eps
                    plus = float(fn(treedef.unflatten(
                        [jnp.asarray(flat.reshape(leaf.shape)) if j == li else leaves[j]
                         for j in range(len(leaves))])))
                    flat[i] = orig - eps
                    minus = float(fn(treedef.unflatten(
                        [jnp.asarray(flat.reshape(leaf.shape)) if j == li else leaves[j]
                         for j in range(len(leaves))])))
                    flat[i] = orig
                    numeric = (plus - minus) / (2 * eps)
                    a = float(an_flat[i])
                    abs_err = abs(a - numeric)
                    denom = max(abs(a), abs(numeric))
                    rel = 0.0 if denom == 0 else abs_err / denom
                    # GradientCheckUtil: pass if relError < maxRelError OR
                    # absError < minAbsoluteError.
                    if rel > max_rel_error and abs_err > min_abs_error:
                        failures.append((path, i, a, numeric))
                    worst = max(worst, rel if abs_err > min_abs_error else 0.0)
                    if verbose:
                        print(f"{path}[{i}]: analytic={a:.8g} numeric={numeric:.8g} rel={rel:.3g}")
            return (len(failures) == 0, worst, failures)


def check_op_gradient(op, *arrays, argnum=0, eps=1e-5, max_rel_error=1e-5,
                      reduce_to_scalar=True, **op_kwargs):
    """Grad-check a raw op w.r.t. one array argument.

    Wraps the op as scalar-valued (sum of outputs) and delegates to
    :func:`check_gradients`.
    """
    arrays = [np.asarray(a, dtype=np.float64) for a in arrays]

    def scalar_fn(p):
        # jnp.asarray inside the x64 context yields f64 to match the perturbed arg
        args = [jnp.asarray(a) for a in arrays]
        args[argnum] = p["x"]
        out = op(*args, **op_kwargs)
        return jnp.sum(out) if reduce_to_scalar else out

    return check_gradients(scalar_fn, {"x": arrays[argnum]}, eps=eps,
                           max_rel_error=max_rel_error)
