"""The comparison that decides ``correct`` for a training cell.

Both sides hand in one *record*::

    {"losses": [l_1 .. l_k],
     "opt":   {step: {leaf: norm of the optimizer's first moment}},
     "delta": {step: {leaf: norm of (parameters after step - initial)}}}

with norms per leaf, taken on the device. The reference adds ``grad1``, the
per-leaf norm of its first gradient. Every number compared is a gap between
the program's reading and the reference's, never the norm of a difference:

``loss1_gap``, ``loss_gap``
    ``|l_p - l_r| / |l_r|`` of the first step (same weights on both sides:
    the forward pass and the loss alone) and the largest over the followed
    steps;
``opt_gap_s<step>``
    worst leaf of ``| ||m_p|| - ||m_r|| | / max(||m_r||, median leaf ||m_r||)``;
    at step 1 this is the first gradient as the optimizer got it;
``delta_gap_s<step>``
    the same for the parameters' change, over the leaves whose first
    reference gradient is at least a thousandth of the median leaf's (a leaf
    whose gradient is nought to rounding moves under Adam by round-off alone);
``opt_medgap_s<step>``, ``delta_medgap_s<step>``
    the median leaf's gap in place of the worst leaf's;
``opt_diff_s<step>``, ``delta_diff_s<step>``
    where both records keep the tensors themselves (``opt_t``, ``delta_t``):
    the norm of the difference over the reference's norm, all leaves taken
    as one vector. A gap of norms is second order in rounding that is random
    from element to element, and reads the same for bfloat16 and for fp8
    operands (``PERF.md`` has the readings); the difference is first order.
"""

from __future__ import annotations

import math
import statistics


def leaf_norms(tree):
    """{leaf: float32 L2 norm} as one device computation over a flat dict."""
    import jax.numpy as jnp
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def to_floats(norms):
    return {k: float(v) for k, v in norms.items()}


def rel_diff(prog_tree, ref_tree) -> float:
    """||prog - ref|| / ||ref|| over all leaves as one vector; the program's
    leaves may be host arrays."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sums(a, b):
        d = sum(jnp.sum(jnp.square(a[k].astype(jnp.float32) - b[k]))
                for k in b)
        n = sum(jnp.sum(jnp.square(b[k])) for k in b)
        return d, n

    shard = {k: getattr(v, "sharding", None) for k, v in ref_tree.items()}
    prog = {k: jax.device_put(prog_tree[k], shard[k]) for k in ref_tree}
    d, n = sums(prog, ref_tree)
    out = float(jnp.sqrt(d / n))
    return out if math.isfinite(out) else math.inf


def leaf_gaps(prog, ref, keep=None) -> dict:
    """{leaf: | ||prog|| - ||ref|| | / max(||ref||, median leaf ||ref||)}."""
    med = statistics.median(ref.values())
    out = {}
    for leaf, r in ref.items():
        if keep is not None and leaf not in keep:
            continue
        gap = abs(prog[leaf] - r) / max(r, med, 1e-30)
        out[leaf] = gap if math.isfinite(gap) else math.inf
    return out


def _gap_numbers(out, name, step, prog, ref, keep=None):
    gaps = leaf_gaps(prog, ref, keep)
    leaf = max(gaps, key=gaps.get)
    out[f"{name}_gap_s{step}"] = {"value": gaps[leaf], "leaf": leaf}
    out[f"{name}_medgap_s{step}"] = {
        "value": statistics.median(gaps.values())}


def numbers(prog: dict, ref: dict) -> dict:
    """{name: {"value", "leaf"?}} for every number the two records allow."""
    out = {}
    k = min(len(prog["losses"]), len(ref["losses"]))
    gaps = [abs(p - r) / max(abs(r), 1e-30)
            for p, r in zip(prog["losses"][:k], ref["losses"][:k])]
    gaps = [g if math.isfinite(g) else math.inf for g in gaps]
    out["loss1_gap"] = {"value": gaps[0]}
    out["loss_gap"] = {"value": max(gaps)}
    g1 = ref["grad1"]
    floor = 1e-3 * statistics.median(g1.values())
    moved = {leaf for leaf, g in g1.items() if g >= floor}
    for step in sorted(ref["opt"]):
        if step in prog["opt"]:
            _gap_numbers(out, "opt", step, prog["opt"][step],
                         ref["opt"][step])
    for step in sorted(ref["delta"]):
        if step in prog["delta"]:
            _gap_numbers(out, "delta", step, prog["delta"][step],
                         ref["delta"][step], keep=moved)
    for kind in ("opt", "delta"):
        for step, tree in ref.get(kind + "_t", {}).items():
            if step in prog.get(kind + "_t", {}):
                out[f"{kind}_diff_s{step}"] = {
                    "value": rel_diff(prog[kind + "_t"][step], tree)}
    return out


def judge(nums: dict, limits: dict):
    """-> (correct, {name: {"value", "limit", "leaf"?}}). Every limit has to
    find its number, and every number that has a limit has to be under it.
    The numbers that have none are listed with ``"limit": None`` for the
    record and judge nothing."""
    compared, ok = {}, True
    for name, limit in limits.items():
        got = nums.get(name)
        if got is None:
            compared[name] = {"value": None, "limit": limit}
            ok = False
            continue
        compared[name] = dict(got, limit=limit)
        if not got["value"] <= limit:
            ok = False
    for name, got in nums.items():
        compared.setdefault(name, dict(got, limit=None))
    return ok, compared
