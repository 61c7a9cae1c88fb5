"""The masked flash kernels behind ``causal_attention`` (ISSUE 36), on the CPU
through the Pallas interpreter: output and all three gradients against one
block of the XLA path on the whole sequence, in the three layouts the decoder
cells send (grouped KV heads under a causal and a window mask, one query head
a KV head with values narrower than the scored width); the dispatch counter
for every decision and fallback reason; a count of the grid steps that
computed against the blocks the mask leaves open; and (ISSUE 38) the output
and the logsumexp kept across a recomputed segment, so that its
recomputation holds no forward kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import memory as memmod
from deeplearning4j_tpu.ops import causal_attention as ca
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.runtime import telemetry as tel

BLOCK, T = 128, 512                       # a sequence of four blocks

#: (query heads, KV heads, scored width, value width, window)
LAYOUTS = {
    "full_g6": (12, 2, 16, 16, None),
    "window_lt_block": (12, 2, 16, 16, 72),
    "window_eq_block": (12, 2, 16, 16, BLOCK),
    "window_gt_block": (12, 2, 16, 16, 200),
    "latent_g1": (2, 2, 192, 128, None),
}


@pytest.fixture
def forced():
    old = fa.set_mode("force")
    yield
    fa.set_mode(old)


def _qkv(layout, dtype, batch=1):
    H, KV, d, dv, _ = LAYOUTS[layout]
    k0 = jax.random.PRNGKey(36)
    q = jax.random.normal(k0, (batch, T, H, d), dtype)
    k = jax.random.normal(jax.random.fold_in(k0, 1), (batch, T, KV, d), dtype)
    v = jax.random.normal(jax.random.fold_in(k0, 2), (batch, T, KV, dv),
                          dtype)
    return q, k, v


def _one_block(q, k, v, window):
    """``_block`` on the whole sequence: the XLA path's own arithmetic."""
    B, _, H, d = q.shape
    KV = k.shape[2]
    hf = lambda a: a.transpose(0, 2, 1, 3)
    out = ca._block(hf(q).reshape(B, KV, H // KV, T, d), hf(k), hf(v), 0, 0,
                    window)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, v.shape[-1])


def _kernel(q, k, v, window, blocks=(BLOCK, BLOCK)):
    hf = lambda a: a.transpose(0, 2, 1, 3)
    return hf(ca.causal_flash(hf(q), hf(k), hf(v), window=window,
                              blocks=blocks, interpret=True))


def _worst(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernel_equals_one_block(layout, dtype, tol):
    window = LAYOUTS[layout][4]
    q, k, v = _qkv(layout, dtype)
    want = _one_block(q, k, v, window)
    got = _kernel(q, k, v, window)
    assert got.shape == want.shape and got.dtype == dtype
    assert _worst(got, want) <= tol

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(
            jnp.sin(fn(*a, window).astype(jnp.float32))), argnums=(0, 1, 2))(
                q, k, v)

    for name, g, w in zip("qkv", grads(_kernel), grads(_one_block)):
        assert g.dtype == dtype
        # a gradient sums over up to 512 positions and 6 heads
        scale = max(1.0, float(jnp.max(jnp.abs(w.astype(jnp.float32)))))
        assert _worst(g, w) <= tol * scale, name


@pytest.mark.parametrize("blocks", [(256, 128), (128, 256), (256, 256)],
                         ids=lambda b: "x".join(map(str, b)))
@pytest.mark.parametrize("window", [None, 200], ids=["full", "window200"])
def test_rectangular_tiles(blocks, window):
    q, k, v = _qkv("full_g6", jnp.float32)
    assert _worst(_kernel(q, k, v, window, blocks),
                  _one_block(q, k, v, window)) <= 1e-5
    g = jax.grad(lambda *a: jnp.sum(_kernel(*a, window, blocks) ** 2),
                 argnums=(0, 1, 2))(q, k, v)
    w = jax.grad(lambda *a: jnp.sum(_one_block(*a, window) ** 2),
                 argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, w):
        assert _worst(a, b) <= 1e-4


@pytest.mark.parametrize("bq,bk,t,window", [
    (128, 128, 512, None), (128, 256, 1024, None), (256, 128, 1024, 100),
    (128, 128, 1024, 128), (128, 128, 1024, 300), (512, 256, 2048, 512),
    (256, 512, 2048, 512), (1024, 1024, 8192, None), (512, 512, 8192, 512)])
def test_block_mask_against_the_whole_mask(bq, bk, t, window):
    """Which blocks a block reaches, from both sides, whether the mask cuts
    through a pair, and the tile inside it: all read off the [t, t] mask."""
    mask = fa.BlockMask(bq, bk, t, window)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    whole = (j <= i) if window is None else (j <= i) & (j > i - window)
    tiles = whole.reshape(t // bq, bq, t // bk, bk).transpose(0, 2, 1, 3)
    has = tiles.any(axis=(2, 3))
    for a in range(mask.nq):
        reach = np.flatnonzero(has[a])
        assert (mask.first_key(a), mask.last_key(a)) == (reach[0], reach[-1])
    for b in range(mask.nk):
        reach = np.flatnonzero(has[:, b])
        assert (mask.first_query(b), mask.last_query(b)) \
            == (reach[0], reach[-1])
    assert mask.open_blocks() == has.sum()
    assert mask.key_span == has.sum(axis=1).max()
    assert mask.query_span == has.sum(axis=0).max()
    for a, b in zip(*np.nonzero(has)):
        assert bool(mask.cuts(a, b)) == (not tiles[a, b].all())
    a, b = np.argwhere(has & ~tiles.all(axis=(2, 3)))[-1]
    np.testing.assert_array_equal(np.asarray(mask.open(a, b)), tiles[a, b])
    np.testing.assert_array_equal(np.asarray(mask.open(a, b, True)),
                                  tiles[a, b].T)


@pytest.mark.parametrize("window", [None, 72, BLOCK, 200],
                         ids=["full", "w72", "w128", "w200"])
def test_closed_blocks_are_skipped_not_masked(monkeypatch, window):
    """Every grid step that computes runs its ``step`` inside
    ``_masked_steps``' guard: count them through a host callback there and
    hold them to the blocks the mask leaves open, in all three kernels. A
    ``where`` over a full grid would count nq * nk a head."""
    ran = []
    real = fa._masked_steps

    def counting(mask, i, j, step):
        def counted(cut):
            jax.debug.callback(lambda a, b: ran.append((int(a), int(b))),
                               i, j)
            step(cut)
        real(mask, i, j, counted)

    monkeypatch.setattr(fa, "_masked_steps", counting)
    H, KV = 4, 2
    k0 = jax.random.PRNGKey(1)
    q = jax.random.normal(k0, (1, H, T, 16))
    k, v = jax.random.normal(jax.random.fold_in(k0, 1), (2, 1, KV, T, 16))
    mask = fa.BlockMask(BLOCK, BLOCK, T, window)
    open_pairs = {(i, j) for i in range(mask.nq)
                  for j in range(mask.first_key(i), mask.last_key(i) + 1)}
    assert len(open_pairs) == mask.open_blocks() < mask.nq * mask.nk

    def run(fn):
        ran.clear()
        jax.block_until_ready(fn(q, k, v))
        jax.effects_barrier()
        return list(ran)

    flash = lambda *a: ca.causal_flash(*a, window=window,
                                       blocks=(BLOCK, BLOCK), interpret=True)
    fwd = run(flash)
    assert len(fwd) == H * len(open_pairs) and set(fwd) == open_pairs
    both = run(jax.grad(lambda *a: jnp.sum(flash(*a) ** 2),
                        argnums=(0, 1, 2)))
    # forward, dq and dk/dv each visit every open pair once a query head
    assert len(both) == 3 * H * len(open_pairs) and set(both) == open_pairs


def _dispatch(**labels):
    return tel.registry.get("attention.dispatch").value(**labels)


@pytest.mark.parametrize("layout,kind,fallback", [
    ("full_g6", "full", "blocked_rows"),
    ("window_lt_block", "window", "blocked_pairs"),
    ("window_gt_block", "window", "blocked_rows"),
    ("latent_g1", "latent", "blocked_rows")])
def test_dispatch_counts_every_decision(monkeypatch, layout, kind, fallback):
    """``kernel`` under ``force``; the XLA path with its reason in ``auto``
    off the chip, under ``off``, in a GSPMD-partitioned trace, for a length
    no kernel block divides, where no tiling fits VMEM and, on a TPU, where
    one query head reads a KV head; ``one_block`` for
    a sequence no longer than the XLA block whatever the mode."""
    window = LAYOUTS[layout][4]
    q, k, v = _qkv(layout, jnp.float32)
    name = None if kind != "latent" else kind
    attend = lambda q, k, v, block=BLOCK: ca.causal_attention(
        q, k, v, window=window, block=block, kind=name)
    want = _one_block(q, k, v, window)

    def counted(labels, run=lambda: attend(q, k, v)):
        before = _dispatch(kind=kind, **labels)
        out = run()
        assert _dispatch(kind=kind, **labels) == before + 1, labels
        return out

    assert fa.mode() == "auto"
    assert _worst(counted(dict(decision=fallback, why="platform")),
                  want) <= 1e-5
    old = fa.set_mode("force")
    try:
        assert _worst(counted(dict(decision="kernel")), want) <= 1e-5
        counted(dict(decision="one_block"),
                lambda: attend(*(a[:, :BLOCK] for a in (q, k, v))))
        # 96 positions tile by an XLA block of 32 (shorter than every
        # window here) and by no kernel block
        short = tuple(a[:, :96] for a in (q, k, v))
        counted(dict(decision="blocked_rows", why="shape"),
                lambda: attend(*short, 32))
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
        with pk.gspmd_trace(mesh):
            counted(dict(decision=fallback, why="gspmd"))
        monkeypatch.setattr(fa, "_VMEM_TILE_BUDGET", 1)
        counted(dict(decision=fallback, why="vmem"))
        monkeypatch.undo()
        fa.set_mode("off")
        counted(dict(decision=fallback, why="mode"))
        if kind == "latent":
            # one query head a KV head: XLA's, even with a TPU in sight
            fa.set_mode("auto")
            monkeypatch.setattr(ca, "_tpu_available", lambda: True)
            counted(dict(decision=fallback, why="ungrouped"))
    finally:
        fa.set_mode(old)


def test_kernel_under_jit_and_a_batch(forced):
    """Two sequences, traced: the dispatcher's layout moves (heads first and
    back) and the ``b // G`` index map over a batch of KV rows."""
    q, k, v = _qkv("full_g6", jnp.float32, batch=2)
    before = _dispatch(kind="full", decision="kernel")
    got = jax.jit(lambda *a: ca.causal_attention(*a, block=BLOCK))(q, k, v)
    assert _dispatch(kind="full", decision="kernel") == before + 1
    assert _worst(got, _one_block(q, k, v, None)) <= 1e-5


@pytest.mark.parametrize("t,d,dv,window,want", [
    (8192, 128, 128, None, 1.15),      # laguna's full layers
    (8192, 128, 128, 512, 2.05),       # laguna's window layers
    (8192, 192, 128, None, 1.15),      # kanana2's latent layers
    (2048, 64, 64, 256, 2.05)])
def test_tiling_rule_computes_few_closed_pairs(t, d, dv, window, want):
    """The tiling comes from the shape and the mask: pairs computed over
    pairs open stays near 1 under a causal mask and about 2 under a window
    no longer than a block, where ``default_blocks`` (the most keys that
    fit) would compute mostly closed ones."""
    blocks = ca.causal_blocks(t, d, dv, window, 2)
    assert blocks is not None and fa.fits_vmem_attention(
        *blocks, max(d, dv), 2)
    mask = fa.BlockMask(*blocks, t, window)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    open_pairs = int(((j <= i) if window is None
                      else (j <= i) & (j > i - window)).sum())
    computed = mask.open_blocks() * mask.bq * mask.bk
    assert 1.0 <= computed / open_pairs <= want
    greedy = fa.default_blocks(t, t, max(d, dv), 2)
    wasteful = fa.BlockMask(*greedy, t, window)
    assert wasteful.open_blocks() * wasteful.bq * wasteful.bk > computed
    assert ca.causal_blocks(t + 8, d, dv, window, 2) is None   # nothing tiles


# ---- kept across a recomputed segment (ISSUE 38) ---------------------------
def _eqns(jaxpr, primitive):
    """Every equation of ``primitive`` in a jaxpr, through the nested ones."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, primitive)


def _two_segments(layout, keep):
    """The gradient of two recomputed segments, each a projection, the
    attention of ``layout`` and an output projection, and its arguments."""
    H, KV, d, dv, window = LAYOUTS[layout]
    kind = "latent" if layout.startswith("latent") else None
    k0 = jax.random.PRNGKey(38)
    x = jax.random.normal(k0, (1, T, 24), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k0, 1),
                          (24, H * d + KV * d + KV * dv), jnp.float32) * 0.2
    wo = jax.random.normal(jax.random.fold_in(k0, 2), (H * dv, 24),
                           jnp.float32) * 0.2

    def segment(w, wo, x):
        y = x @ w
        q = y[..., :H * d].reshape(1, T, H, d)
        k = y[..., H * d:(H + KV) * d].reshape(1, T, KV, d)
        v = y[..., (H + KV) * d:].reshape(1, T, KV, dv)
        o = ca.causal_attention(q, k, v, window=window, block=BLOCK,
                                kind=kind, keep=keep)
        return x + o.reshape(1, T, H * dv) @ wo

    def loss(w, wo, x):
        for _ in range(2):
            x = memmod.checkpoint(segment, memmod.resolve_policy("full"))(
                w, wo, x)
        return jnp.sum(jnp.sin(x))

    return jax.grad(loss, argnums=(0, 1)), (w, wo, x)


def _kernel_names(jaxpr):
    return [e.params["name"] for e in _eqns(jaxpr, "pallas_call")]


@pytest.mark.parametrize("layout", ["full_g6", "window_gt_block",
                                    "latent_g1"])
def test_kernel_path_keeps_output_and_logsumexp(forced, layout):
    """The backward kernels read the output and the logsumexp: with both
    tagged the gradient holds one forward kernel a segment (the forward
    pass's) where it held two, the backward kernels unchanged, and the
    gradients are equal to the last bit."""
    kind = {"full_g6": "full", "window_gt_block": "window",
            "latent_g1": "latent"}[layout]
    counter = tel.registry.get("attention.kept")
    before = counter.value(kind=kind, decision="kept")
    grad, args = _two_segments(layout, keep=True)
    kept = jax.make_jaxpr(grad)(*args).jaxpr
    # the two segments are one traced site: the same function and shapes
    assert counter.value(kind=kind, decision="kept") == before + 1
    names = _kernel_names(kept)
    assert names.count("causal_flash_fwd") == 2
    assert names.count("causal_flash_bwd_dq") == 2
    assert names.count("causal_flash_bwd_dkv") == 2
    tags = [e for e in _eqns(kept, "name")
            if e.params["name"] == memmod.KEPT]
    # the output [rows, T, dv] and the logsumexp [rows, 1, T] of each
    shapes = sorted({tuple(e.outvars[0].aval.shape) for e in tags})
    H, _, _, dv, _ = LAYOUTS[layout]
    assert shapes == sorted({(H, T, dv), (H, 1, T)})
    before = counter.value(kind=kind, decision="recomputed", why="wide")
    again_grad, _ = _two_segments(layout, keep=False)
    again = jax.make_jaxpr(again_grad)(*args).jaxpr
    assert counter.value(kind=kind, decision="recomputed",
                         why="wide") == before + 1
    assert _kernel_names(again).count("causal_flash_fwd") == 4
    assert not [e for e in _eqns(again, "name")
                if e.params["name"] == memmod.KEPT]
    for a, b in zip(grad(*args), again_grad(*args)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_blocked_xla_path_keeps_the_output_alone():
    """Off the chip in ``auto`` the same segments take the blocked rows:
    one tag a site, on the ``[B, T, H, dv]`` result, and the recomputation's
    map over the rows is gone (one ``scan`` a segment fewer)."""
    grad, args = _two_segments("full_g6", keep=True)
    kept = jax.make_jaxpr(grad)(*args).jaxpr
    tags = [e for e in _eqns(kept, "name")
            if e.params["name"] == memmod.KEPT]
    H, _, _, dv, _ = LAYOUTS["full_g6"]
    assert {tuple(e.outvars[0].aval.shape) for e in tags} == {(1, T, H, dv)}
    again = jax.make_jaxpr(_two_segments("full_g6", keep=False)[0])(
        *args).jaxpr
    assert len(list(_eqns(again, "scan"))) \
        - len(list(_eqns(kept, "scan"))) == 2


def test_a_caller_outside_a_segment_keeps_nothing(forced):
    """``keep=True`` with no recomputing policy around the call: no tag, on
    either path, and ``why=no_policy``."""
    q, k, v = _qkv("full_g6", jnp.float32)
    counter = tel.registry.get("attention.kept")
    before = counter.value(kind="full", decision="recomputed",
                           why="no_policy")
    fn = jax.grad(lambda *a: jnp.sum(ca.causal_attention(
        *a, block=BLOCK, keep=True) ** 2), argnums=(0, 1, 2))
    assert not list(_eqns(jax.make_jaxpr(fn)(q, k, v).jaxpr, "name"))
    assert counter.value(kind="full", decision="recomputed",
                         why="no_policy") == before + 1
    # and under a policy that recomputes nothing
    none = memmod.checkpoint(fn, memmod.resolve_policy("none"))
    assert none is fn and not memmod.recomputing()
