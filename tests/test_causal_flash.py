"""The masked flash kernels behind ``causal_attention`` (ISSUE 36), on the CPU
through the Pallas interpreter: output and all three gradients against one
block of the XLA path on the whole sequence, in the three layouts the decoder
cells send (grouped KV heads under a causal and a window mask, one query head
a KV head with values narrower than the scored width); the dispatch counter
for every decision and fallback reason; a count of the grid steps that
computed against the blocks the mask leaves open; and (ISSUE 38) the output
and the logsumexp kept across a recomputed segment, so that its
recomputation holds no forward kernel; (ISSUE 42) the same kernels under a
mask that is data (``select=``), the query heads of a KV head stacked in one
tile, against the blocked XLA path and a direct masked softmax."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import memory as memmod
from deeplearning4j_tpu.ops import causal_attention as ca
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.ops import sparse_attention as sa
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


@pytest.mark.parametrize("window,select", [(None, False), (72, False),
                                           (BLOCK, False), (200, False),
                                           (None, True)],
                         ids=["full", "w72", "w128", "w200", "select"])
def test_closed_blocks_are_skipped_not_masked(monkeypatch, window, select):
    """Every grid step that computes runs its ``step`` inside
    ``_masked_steps``' guard: count them through a host callback there and
    hold them to the blocks the mask leaves open, in all three kernels. A
    ``where`` over a full grid would count nq * nk a head. Under a mask that
    is data a step computes the query heads of a KV head at once: a KV head
    counts each pair once, so its mask tile is fetched once for them all."""
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

    sel = jnp.tril(jnp.ones((1, T, T), bool)) if select else None
    flash = lambda *a: ca.causal_flash(*a, window=window,
                                       blocks=(BLOCK, BLOCK), interpret=True,
                                       select=sel)
    rows = KV if select else H
    fwd = run(flash)
    assert len(fwd) == rows * len(open_pairs) and set(fwd) == open_pairs
    both = run(jax.grad(lambda *a: jnp.sum(flash(*a) ** 2),
                        argnums=(0, 1, 2)))
    # forward, dq and dk/dv each visit every open pair once a query head (a
    # KV head under a selection)
    assert len(both) == 3 * rows * len(open_pairs) \
        and set(both) == open_pairs


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


# ---- a mask that is data (ISSUE 42) ---------------------------------------
#: (query heads, KV heads): 8 and 2 query heads a KV head
GROUPS = {"g8": (16, 2), "g2": (4, 2)}


def _index(seed, rounded=False, silent_rows=()):
    """The indexer's inputs for two sequences of ``T``: 3 index heads of 4.
    ``rounded`` makes many equal scores; the index weights of
    ``silent_rows`` are nought, so those rows score every key 0."""
    k0 = jax.random.PRNGKey(seed)
    qi = jax.random.normal(k0, (2, T, 3, 4))
    ki = jax.random.normal(jax.random.fold_in(k0, 1), (2, T, 4))
    wi = jax.random.normal(jax.random.fold_in(k0, 2), (2, T, 3))
    if rounded:
        qi, ki, wi = jnp.round(qi), jnp.round(ki), jnp.round(wi)
    return qi, ki, wi.at[:, list(silent_rows)].set(0.0)


def _late_keys():
    """Every row opens its own key and the three before it and nothing
    else: from the third block on, whole tiles of a row are closed before
    its first open key."""
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    return jnp.asarray(np.broadcast_to((j <= i) & (j > i - 4), (2, T, T)))


#: name -> the mask of two sequences of T
SELECTIONS = {
    "open_keys": lambda: sa.open_keys(*_index(42), 200)[0],
    "closed_tiles_first": _late_keys,
    # rows BLOCK - 1 and BLOCK straddle the topk boundary at a tile's edge
    "topk_boundary": lambda: sa.open_keys(*_index(43), BLOCK)[0],
    # many ties, and rows 300 and 301 tie on every key: their topk lowest
    "ties": lambda: sa.open_keys(*_index(44, True, (300, 301)), 150)[0],
}


def _selected_qkv(group, seed=42):
    H, KV = GROUPS[group]
    k0 = jax.random.PRNGKey(seed)
    return (jax.random.normal(k0, (2, T, H, 16)),
            jax.random.normal(jax.random.fold_in(k0, 1), (2, T, KV, 16)),
            jax.random.normal(jax.random.fold_in(k0, 2), (2, T, KV, 16)))


def _direct(mask):
    """A plain softmax over the open keys, every head on its KV head."""
    def fn(q, k, v):
        G = q.shape[2] // k.shape[2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, G, axis=2),
                       precision="highest") / 4.0
        p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, G, axis=2),
                          precision="highest")
    return fn


def _selected_kernel(mask, blocks):
    hf = lambda a: a.transpose(0, 2, 1, 3)
    return lambda q, k, v: hf(ca.causal_flash(
        hf(q), hf(k), hf(v), blocks=blocks, interpret=True, select=mask))


def _blocked_rows(mask):
    """``_rows_select``, the XLA path's blocks under the selection."""
    def fn(q, k, v):
        old = fa.set_mode("off")
        try:
            return ca.causal_attention(q, k, v, block=BLOCK, select=mask)
        finally:
            fa.set_mode(old)
    return fn


@pytest.mark.parametrize("blocks", [(BLOCK, BLOCK), (BLOCK, 2 * BLOCK)],
                         ids=["128x128", "128x256"])
@pytest.mark.parametrize("selection", list(SELECTIONS))
@pytest.mark.parametrize("group", list(GROUPS))
def test_selected_kernels_equal_blocked_rows_and_a_softmax(group, selection,
                                                           blocks):
    """Output and the q / k / v gradients of the kernels under a mask that
    is data, over four query blocks and two or four key blocks, against
    ``_rows_select`` and a direct softmax over the open keys."""
    mask = SELECTIONS[selection]()
    assert bool(jnp.all(jnp.any(mask, axis=-1)))     # every row has a key
    q, k, v = _selected_qkv(group)
    fns = [_selected_kernel(mask, blocks), _blocked_rows(mask),
           _direct(mask)]
    outs = [fn(q, k, v) for fn in fns]
    assert _worst(outs[0], outs[1]) <= 1e-5
    assert _worst(outs[0], outs[2]) <= 1e-5

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2))(q, k, v)

    got, *wants = [grads(fn) for fn in fns]
    for want in wants:
        for name, g, w in zip("qkv", got, want):
            scale = max(1.0, float(jnp.max(jnp.abs(w))))
            assert _worst(g, w) <= 1e-5 * scale, name


def test_a_selection_is_dispatched_as_any_site(monkeypatch):
    """``select=`` goes through the same reasons as a static mask: the
    kernels under ``force``, in a GSPMD-partitioned trace the XLA path,
    where no stacked tiling fits VMEM ``vmem``, one query head a KV head on
    a TPU ``ungrouped``; and it still has no window."""
    mask = SELECTIONS["open_keys"]()
    q, k, v = _selected_qkv("g8")
    counter = tel.registry.get("attention.dispatch")

    def counted(run, **labels):
        before = counter.value(kind="sparse", **labels)
        out = run()
        assert counter.value(kind="sparse", **labels) == before + 1, labels
        return out

    attend = lambda *a: ca.causal_attention(*a, block=BLOCK, select=mask)
    old = fa.set_mode("force")
    try:
        got = counted(lambda: attend(q, k, v), decision="kernel")
        assert _worst(got, _direct(mask)(q, k, v)) <= 1e-5
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
        with pk.gspmd_trace(mesh):
            counted(lambda: attend(q, k, v), decision="blocked_rows",
                    why="gspmd")
        monkeypatch.setattr(fa, "_VMEM_TILE_BUDGET", 1)
        counted(lambda: attend(q, k, v), decision="blocked_rows", why="vmem")
        monkeypatch.undo()
        fa.set_mode("auto")
        monkeypatch.setattr(ca, "_tpu_available", lambda: True)
        one = k.shape[2]
        counted(lambda: attend(q[:, :, :one], k, v), decision="blocked_rows",
                why="ungrouped")
    finally:
        fa.set_mode(old)
    with pytest.raises(ValueError, match="window"):
        ca.causal_flash(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)),
                        window=200, select=mask)


@pytest.mark.parametrize("t,group,want", [
    (8192, 8, (256, 1024)),          # keye: 32 heads on 4 KV heads of 128
    (8192, 2, (1024, 1024)), (8192, 16, (128, 1024)), (512, 8, (512, 256))])
def test_stacked_tiling_counts_the_mask_and_the_heads(t, group, want):
    """Under a selection the tile stacks the group's heads and fetches an
    8-bit mask tile beside the keys: the rule counts both, and its choice
    at Keye's shape is the fastest of the four the chip measured (PERF.md,
    PR 42). Without a selection nothing changes."""
    got = ca.causal_blocks(t, 128, 128, None, 2, group)
    assert got == want
    bq, bk = got
    assert fa.fits_vmem_attention(group * bq, bk, 128, 2, mask_rows=bq)
    assert fa.vmem_bytes_attention(group * bq, bk, 128, 2, mask_rows=bq) \
        == fa.vmem_bytes_attention(group * bq, bk, 128, 2) + 2 * bq * bk
    assert ca.causal_blocks(t, 128, 128, None, 2) == \
        ca.causal_blocks(t, 128, 128, None, 2, 0)


def test_selected_kernels_keep_output_logsumexp_and_mask(forced):
    """Inside a recomputed segment the kept set is the output, the
    logsumexp and the mask: the gradient holds one forward kernel a segment
    (the forward pass's), and equals the one that recomputes it bit for
    bit."""
    mask = SELECTIONS["open_keys"]()[:1]
    H, KV = GROUPS["g2"]
    k0 = jax.random.PRNGKey(38)
    x = jax.random.normal(k0, (1, T, 24), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k0, 1),
                          (24, (H + 2 * KV) * 16), jnp.float32) * 0.2
    wo = jax.random.normal(jax.random.fold_in(k0, 2), (H * 16, 24),
                           jnp.float32) * 0.2

    def make(keep):
        def segment(w, wo, x):
            y = x @ w
            q = y[..., :H * 16].reshape(1, T, H, 16)
            k = y[..., H * 16:(H + KV) * 16].reshape(1, T, KV, 16)
            v = y[..., (H + KV) * 16:].reshape(1, T, KV, 16)
            o = ca.causal_attention(q, k, v, block=BLOCK, keep=keep,
                                    select=mask)
            return x + o.reshape(1, T, H * 16) @ wo

        def loss(w, wo, x):
            for _ in range(2):
                x = memmod.checkpoint(segment, memmod.resolve_policy("full"))(
                    w, wo, x)
            return jnp.sum(jnp.sin(x))
        return jax.grad(loss, argnums=(0, 1))

    kept = jax.make_jaxpr(make(True))(w, wo, x).jaxpr
    names = _kernel_names(kept)
    assert names.count("causal_flash_fwd") == 2
    assert names.count("causal_flash_bwd_dq") == 2
    tags = {tuple(e.outvars[0].aval.shape) for e in _eqns(kept, "name")
            if e.params["name"] == memmod.KEPT}
    assert tags == {(H, T, 16), (H, 1, T), (1, T, T)}
    assert _kernel_names(jax.make_jaxpr(make(False))(w, wo, x).jaxpr) \
        .count("causal_flash_fwd") == 4
    for a, b in zip(make(True)(w, wo, x), make(False)(w, wo, x)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
