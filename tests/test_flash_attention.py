"""Flash attention (ISSUE 3): fused Pallas kernel parity (interpret mode on
the CPU mesh — the REAL kernel code, per-block online softmax and the
custom-VJP backward), dispatch guard + zero-silent-fallback counters, the
attention layers' fused routing, the f32-softmax numerics fix, and the
SameDiff attention-pattern fusion pass."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import ops
from deeplearning4j_tpu.ops import flash_attention as fa


@pytest.fixture
def force_mode():
    """Route dispatch through the kernel (interpret off-TPU) for the test."""
    old = fa.set_mode("force")
    fa.reset_counters()
    yield
    fa.set_mode(old)


def _qkv(rng, B=2, H=2, Tq=128, Tk=128, d=32, dtype=np.float32):
    mk = lambda T: jnp.asarray(rng.normal(size=(B, H, T, d)), dtype=dtype)
    return mk(Tq), mk(Tk), mk(Tk)


def _ragged_bias(rng, B, Tk, full_mask_row=True):
    """Ragged per-row key masks, incl. one fully-masked batch row."""
    mask = np.ones((B, Tk), np.float32)
    for b in range(B):
        mask[b, Tk - 1 - (b * 7) % (Tk // 2):] = 0.0
    if full_mask_row:
        mask[0, :] = 0.0
    return jnp.where(jnp.asarray(mask)[:, None, None, :] > 0, 0.0,
                     jnp.asarray(np.finfo(np.float32).min))


@pytest.mark.parametrize("dtype,tol", [(np.float32, 1e-5),
                                       ("bfloat16", 2e-2)])
def test_flash_forward_parity(rng, dtype, tol):
    """Fused forward == einsum reference across dtypes, ragged key masks
    incl. a fully-masked batch row, Tq != Tk, head dim != lane width."""
    q, k, v = _qkv(rng, Tq=128, Tk=256, d=48, dtype=dtype)
    bias = _ragged_bias(rng, 2, 256)
    ref = fa.reference_attention(q, k, v, bias)
    out = fa.flash_attention(q, k, v, bias, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol)
    # no-bias path too
    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(q, k, v, interpret=True), np.float32),
        np.asarray(fa.reference_attention(q, k, v), np.float32), atol=tol)
    ops.mark_fwd_tested("attention.fused_sdpa")


def test_flash_multiblock_online_softmax(rng):
    """Several q AND kv blocks per row: the running max/sum accumulators do
    real cross-block corrections (block sizes forced below T)."""
    q, k, v = _qkv(rng, Tq=64, Tk=64, d=16)
    ref = fa.reference_attention(q, k, v)
    out = fa.flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_gradient_parity(rng):
    """Custom-VJP backward (recompute from saved softmax stats) == autodiff
    through the reference path, masked rows included, f32 atol 1e-5."""
    q, k, v = _qkv(rng, Tq=128, Tk=128, d=32)
    bias = _ragged_bias(rng, 2, 128)

    def loss(path, q, k, v):
        return jnp.sum(jnp.sin(path(q, k, v, bias)))

    gf = jax.grad(
        lambda *a: loss(lambda q, k, v, b: fa.flash_attention(
            q, k, v, b, interpret=True), *a), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: loss(fa.reference_attention, *a),
                  argnums=(0, 1, 2))(q, k, v)
    for got, ref in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)
    ops.mark_grad_tested("attention.fused_sdpa")


def test_flash_gradient_parity_bf16(rng):
    q, k, v = _qkv(rng, Tq=64, Tk=64, d=32, dtype="bfloat16")
    gf = jax.grad(lambda x: jnp.sum(fa.flash_attention(
        x, k, v, interpret=True).astype(jnp.float32)))(q)
    gr = jax.grad(lambda x: jnp.sum(
        fa.reference_attention(x, k, v).astype(jnp.float32)))(q)
    np.testing.assert_allclose(np.asarray(gf, np.float32),
                               np.asarray(gr, np.float32), atol=5e-2)


def _parity(q, k, v, bias, tol, **blocks):
    """Forward and all three gradients of the kernel path against the
    reference, through a loss that keeps the forward alive."""
    def loss(path, q, k, v):
        return jnp.sum(jnp.sin(path(q, k, v).astype(jnp.float32)))

    fused = lambda q, k, v: fa.flash_attention(q, k, v, bias, interpret=True,
                                               **blocks)
    ref = lambda q, k, v: fa.reference_attention(q, k, v, bias)
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_allclose(f32(fused(q, k, v)), f32(ref(q, k, v)),
                               atol=tol)
    gf = jax.grad(lambda *a: loss(fused, *a), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: loss(ref, *a), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(gf, gr):
        np.testing.assert_allclose(f32(got), f32(want), atol=4 * tol)


@pytest.mark.parametrize("has_bias", [False, True], ids=["nobias", "keybias"])
@pytest.mark.parametrize("dtype,tol", [(np.float32, 1e-5),
                                       ("bfloat16", 2e-2)])
def test_whole_row_tile_parity(rng, dtype, tol, has_bias):
    """The default tiling of a short row is one tile a head (nk = 1): the
    whole-row forward and the fused backward, which keeps no statistics and
    works the softmax out again from the tile. A fully masked batch row
    (every key at finfo.min) must still read UNIFORM attention in both
    directions, the contract the blocked kernels keep with their two-piece
    logsumexp. Tq != Tk, four heads a grid step."""
    q, k, v = _qkv(rng, H=4, Tq=64, Tk=128, d=32, dtype=dtype)
    assert fa.default_blocks(64, 128, 32, q.dtype.itemsize, has_bias) \
        == (64, 128)
    assert fa.heads_per_step(4, 64, 128, 32) == 4
    bias = _ragged_bias(rng, 2, 128) if has_bias else None
    _parity(q, k, v, bias, tol)


def test_whole_row_tile_over_query_blocks(rng):
    """A whole key row under several query blocks (explicit block_q): dk
    and dv accumulate over the inner grid axis in f32 scratch."""
    q, k, v = _qkv(rng, Tq=128, Tk=64, d=16)
    _parity(q, k, v, _ragged_bias(rng, 2, 64), 1e-5, block_q=32, block_k=64)


@pytest.mark.parametrize("has_bias", [False, True], ids=["nobias", "keybias"])
def test_long_row_keeps_the_blocked_grid(rng, has_bias, monkeypatch):
    """A key row that does not fit beside its query block keeps a blocked
    grid: the online-softmax forward and the dq and dk/dv kernels, with the
    logsumexp saved in its two pieces (a fully masked row included). On the
    chip that is past 4,096 keys; here the budget is cut so that 512 keys
    are too many."""
    T = 512
    monkeypatch.setattr(fa, "_VMEM_TILE_BUDGET", 3 << 19)
    blocks = fa.default_blocks(T, T, 8, 4, has_bias)
    assert blocks == (256, 256) and fa.tiling_kind(blocks[1], T) == "blocked"
    q, k, v = _qkv(rng, B=2, H=1, Tq=T, Tk=T, d=8)
    fa._TILING.zero()
    _parity(q, k, v, _ragged_bias(rng, 2, T) if has_bias else None, 1e-5)
    assert fa._TILING.value(kind="blocked") > 0
    assert fa._TILING.value(kind="whole_row") == 0


# (Tq, Tk, d, itemsize, has_bias) -> (block_q, block_k), kind
_TILING_TABLE = [
    ((512, 512, 64, 2, True), (512, 512), "whole_row"),   # BERT-base s512
    ((128, 128, 64, 2, True), (128, 128), "whole_row"),   # ... s128
    ((128, 128, 64, 4, False), (128, 128), "whole_row"),
    ((384, 384, 64, 2, True), (384, 384), "whole_row"),
    ((1024, 1024, 64, 4, True), (512, 1024), "whole_row"),
    ((2048, 2048, 64, 2, True), (512, 2048), "whole_row"),
    ((2048, 2048, 128, 4, True), (512, 2048), "whole_row"),
    ((4096, 4096, 64, 2, False), (512, 4096), "whole_row"),
    ((8192, 8192, 64, 2, True), (512, 4096), "blocked"),
    ((8192, 8192, 128, 2, False), (512, 2048), "blocked"),
    ((128, 16384, 64, 2, True), (128, 8192), "blocked"),
    ((120, 120, 16, 4, False), (120, 120), "whole_row"),
    ((1200, 1200, 64, 2, False), (400, 1200), "whole_row"),  # 1200 = 3 x 400
    ((640, 640, 64, 2, True), (320, 640), "whole_row"),
    ((100, 100, 16, 4, False), None, None),               # nothing tiles
    ((192, 192, 16, 4, True), (192, 192), "whole_row"),
]


@pytest.mark.parametrize("key,blocks,kind", _TILING_TABLE,
                         ids=[f"{k[0]}x{k[1]}d{k[2]}b{k[3]}"
                              f"{'bias' if k[4] else ''}"
                              for k, _, _ in _TILING_TABLE])
def test_default_tiling_table(key, blocks, kind, force_mode):
    """One function decides the default tile. What it picks tiles, passes
    the VMEM guard, is what the autotuner seeds, is what the dispatcher
    admitted and what ``flash_attention()`` then takes (traced, so no
    sweep could run), and the site's tiling is counted."""
    from deeplearning4j_tpu.ops import autotune as at
    tq, tk, d, itemsize, has_bias = key
    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    assert fa.default_blocks(*key) == blocks
    q = jax.ShapeDtypeStruct((2, 4, tq, d), dtype)
    kv = jax.ShapeDtypeStruct((2, 4, tk, d), dtype)
    bias = jax.ShapeDtypeStruct((2, 1, 1, tk), jnp.float32) \
        if has_bias else None
    at.reset()
    fa._TILING.zero()
    try:
        jax.eval_shape(lambda *a: fa.attention(*a), q, kv, kv, bias)
    finally:
        seeded = at.lookup(tq, tk, d, dtype, has_bias)
        at.reset()
    c = fa.counters()
    if blocks is None:
        assert c["fallback_shape"] == 1 and c["fused"] == 0
        assert seeded is None
        return
    bq, bk = blocks
    assert tq % bq == 0 and tk % bk == 0 and bq % 8 == 0
    assert bq <= fa.MAX_BLOCK
    assert fa.kv_block_ok(bk, tk, has_bias)
    assert fa.fits_vmem_attention(bq, bk, d, itemsize)
    assert c["fused"] == 1
    assert seeded == {"blocks": [bq, bk], "source": "default"}
    assert fa.tiling_kind(bk, tk) == kind
    other = "blocked" if kind == "whole_row" else "whole_row"
    assert fa._TILING.value(kind=kind) == 1
    assert fa._TILING.value(kind=other) == 0


def test_dispatcher_never_admits_a_tile_the_kernel_refuses(force_mode,
                                                           monkeypatch):
    """``_route`` and ``flash_attention()`` read the same rule: over a
    grid of lengths, head sizes, dtypes and biases, whatever the dispatcher
    sends to the kernel the kernel takes (no ValueError), and where the rule
    finds no tile the decision is a counted fallback. The same with a VMEM
    budget so small that large tiles are refused: the rule then yields a
    smaller tile, or the dispatcher a ``fallback_vmem``."""
    from deeplearning4j_tpu.ops import autotune as at

    def sweep():
        fused = fell = 0
        for tq, tk in [(8, 8), (64, 200), (96, 96), (100, 128), (256, 512),
                       (384, 384), (512, 640), (1000, 1000), (1024, 4096)]:
            for d in (16, 64, 256):
                for dtype in (jnp.float32, jnp.bfloat16):
                    for has_bias in (False, True):
                        q = jax.ShapeDtypeStruct((1, 2, tq, d), dtype)
                        kv = jax.ShapeDtypeStruct((1, 2, tk, d), dtype)
                        b = jnp.zeros((1, 1, 1, tk)) if has_bias else None
                        at.reset()
                        route = fa._route(q, kv, kv, b)
                        rule = fa.default_blocks(
                            tq, tk, d, np.dtype(dtype).itemsize, has_bias)
                        assert (route is None) == (rule is not None)
                        # a fresh function: eval_shape keeps its traces
                        kernel = lambda *a: fa.flash_attention(*a)
                        if route is None:
                            jax.eval_shape(kernel, q, kv, kv, b)
                            fused += 1
                        else:
                            assert route in ("fallback_shape",
                                             "fallback_vmem")
                            with pytest.raises(ValueError):
                                jax.eval_shape(kernel, q, kv, kv, b)
                            fell += 1
        at.reset()
        return fused, fell

    fused, fell = sweep()
    assert fused >= 80 and fell >= 12
    monkeypatch.setattr(fa, "_VMEM_TILE_BUDGET", 1 << 20)
    assert fa.default_blocks(512, 512, 64, 2, True) == (128, 128)
    assert fa.default_blocks(2048, 2048, 64, 2, True) == (128, 128)
    assert fa._route(jax.ShapeDtypeStruct((1, 2, 8, 2048), jnp.float32),
                     *[jax.ShapeDtypeStruct((1, 2, 8, 2048), jnp.float32)] * 2,
                     None) == "fallback_vmem"
    fused_small, _ = sweep()
    assert 0 < fused_small < fused


def test_heads_per_step_and_vmem_accounting():
    """A short row takes several heads a grid step, never across a batch
    row; the accounting doubles the fetched blocks only and asks the
    compiler for more than its default only where the count says so."""
    assert fa.heads_per_step(12, 512, 512, 64) == 1
    assert fa.heads_per_step(12, 128, 128, 64) == 12
    assert fa.heads_per_step(12, 256, 256, 64) == 4
    assert fa.heads_per_step(7, 128, 128, 64) == 7
    assert fa.heads_per_step(16, 128, 256, 64) == 8
    assert fa.heads_per_step(5, 64, 64, 64) == 5
    assert fa.heads_per_step(12, 512, 4096, 64, 2) == 1
    assert fa.heads_per_step(12, 128, 128, 1024, 4) == 3   # VMEM, not room
    for heads in (1, 5, 12, 16):
        for t in (8, 64, 128, 384, 512):
            hb = fa.heads_per_step(heads, t, t, 64)
            assert heads % hb == 0 and (hb == 1
                                        or hb * t * t <= fa.MAX_BLOCK ** 2)
    one = fa.vmem_bytes_attention(512, 512, 64, 2)
    assert 4 << 20 < one < fa._SCOPED_VMEM * 3 // 4
    assert fa.vmem_bytes_attention(128, 128, 64, 2, hb=12) < 2 * one
    _, pltpu = fa._load_pallas()
    assert fa._compiler_params(pltpu, vmem_bytes=one).vmem_limit_bytes is None
    big = fa.vmem_bytes_attention(512, 512, 512, 4)
    assert fa.fits_vmem_attention(512, 512, 512, 4)
    assert fa._compiler_params(pltpu, ("parallel", "arbitrary"), big) \
        .vmem_limit_bytes == big * 3 // 2
    assert not fa.fits_vmem_attention(512, 512, 4096, 4)


def test_flash_raises_on_non_tiling_and_bad_bias(rng):
    q, k, v = _qkv(rng, Tq=100, Tk=128, d=16)
    with pytest.raises(ValueError, match="do not tile"):
        fa.flash_attention(q, k, v, interpret=True)
    q, k, v = _qkv(rng, Tq=128, Tk=128, d=16)
    bad_bias = jnp.zeros((2, 2, 128, 128))  # per-head/query: not reducible
    with pytest.raises(ValueError, match="key-reducible"):
        fa.flash_attention(q, k, v, bad_bias, interpret=True)


def test_dispatch_fallbacks_and_counters(rng, force_mode):
    """Every fallback routes to the reference path WITH a counter bump —
    the zero-silent-fallback contract — and fused output still matches."""
    # non-power-of-two T -> fallback_shape, output == reference exactly
    q, k, v = _qkv(rng, Tq=100, Tk=100, d=16)
    out = fa.attention(q, k, v)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(fa.reference_attention(q, k, v)))
    assert fa.counters()["fallback_shape"] == 1
    # per-query bias -> fallback_bias
    q, k, v = _qkv(rng, Tq=32, Tk=32, d=16)
    fa.attention(q, k, v, jnp.zeros((2, 2, 32, 32)))
    assert fa.counters()["fallback_bias"] == 1
    # int dtype -> fallback_dtype
    fa.attention(q.astype(jnp.int32), k.astype(jnp.int32),
                 v.astype(jnp.int32))
    assert fa.counters()["fallback_dtype"] == 1
    # tiling shape under force -> the kernel path, counted
    before = fa.counters()["fused"]
    out = fa.attention(q, k, v)
    assert fa.counters()["fused"] == before + 1
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(fa.reference_attention(q, k, v)),
        atol=1e-5)


def test_dispatch_cpu_auto_falls_back(rng):
    """auto mode off-TPU: reference path, counted as fallback_platform —
    and 'off' forces the reference path everywhere."""
    old = fa.set_mode("auto")
    fa.reset_counters()
    try:
        q, k, v = _qkv(rng, Tq=32, Tk=32, d=16)
        fa.attention(q, k, v)
        assert fa.counters()["fallback_platform"] == 1
        fa.set_mode("off")
        fa.attention(q, k, v)
        assert fa.counters()["fallback_mode"] == 1
    finally:
        fa.set_mode(old)
    with pytest.raises(ValueError, match="mode"):
        fa.set_mode("sometimes")


def test_kernel_path_taken_in_tier1(rng, force_mode):
    """CI guard (ISSUE 3 satellite): the tier-1 suite must exercise the
    REAL kernel code path (interpret mode) — dispatch counters prove the
    fused route was taken, not a silent fallback."""
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer

    lyr = SelfAttentionLayer(n_out=32, n_heads=2)
    params, state, _ = lyr.initialize(jax.random.PRNGKey(0), (64, 32),
                                      jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 64, 32)).astype(np.float32))
    lyr.apply(params, x, state)
    c = fa.counters()
    assert c["fused"] >= 1, f"layer did not reach the kernel: {c}"
    assert sum(v for k, v in c.items() if k.startswith("fallback")) == 0


def test_attention_layer_fused_matches_einsum(rng, force_mode):
    """SelfAttentionLayer routed through the kernel == the einsum path,
    with the masked-step zero-output contract preserved."""
    from deeplearning4j_tpu.nn.layers.attention import (
        LearnedSelfAttentionLayer, SelfAttentionLayer)

    lyr = SelfAttentionLayer(n_out=32, n_heads=4, has_bias=True)
    params, state, _ = lyr.initialize(jax.random.PRNGKey(1), (64, 32),
                                      jnp.float32)
    x = jnp.asarray(rng.normal(size=(3, 64, 32)).astype(np.float32))
    mask = np.ones((3, 64), np.float32)
    mask[0, 40:] = 0.0
    mask[2, 5:] = 0.0
    mask = jnp.asarray(mask)

    y_fused, _, _ = lyr.apply(params, x, state, mask=mask)
    assert fa.counters()["fused"] >= 1
    fa.set_mode("off")
    y_ref, _, _ = lyr.apply(params, x, state, mask=mask)
    fa.set_mode("force")
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_ref),
                               atol=1e-5)
    # masked steps emit zeros (DL4J contract)
    assert np.all(np.asarray(y_fused)[0, 40:] == 0.0)
    assert np.all(np.asarray(y_fused)[2, 5:] == 0.0)

    # learned queries: tiny Tq does not tile -> guarded fallback, same math
    lq = LearnedSelfAttentionLayer(n_out=32, n_heads=2, n_queries=3)
    p2, s2, _ = lq.initialize(jax.random.PRNGKey(2), (64, 32), jnp.float32)
    y2, _, _ = lq.apply(p2, x, s2, mask=mask)
    assert fa.counters()["fallback_shape"] >= 1
    fa.set_mode("off")
    y2_ref, _, _ = lq.apply(p2, x, s2, mask=mask)
    fa.set_mode("force")
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y2_ref),
                               atol=1e-6)


def test_mha_bf16_softmax_upcast_shrinks_f32_gap(rng):
    """Numerics-fix regression (ISSUE 3 satellite): _mha now upcasts
    scores to f32 before softmax; under the bf16 policy the gap to the
    f32 oracle must SHRINK vs the old storage-dtype softmax."""
    from deeplearning4j_tpu.nn.layers.attention import (_heads_join,
                                                        _heads_split, _mha)

    B, T, D, Hn = 2, 32, 32, 2
    x32 = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32)) * 3.0
    params32 = {n: jnp.asarray(rng.normal(size=(D, D)).astype(np.float32))
                / np.sqrt(D) for n in ("Wq", "Wk", "Wv", "Wo")}
    oracle = np.asarray(_mha(x32, x32, params32, Hn, None))

    x16 = x32.astype(jnp.bfloat16)
    params16 = {n: w.astype(jnp.bfloat16) for n, w in params32.items()}
    new_gap = float(np.max(np.abs(
        np.asarray(_mha(x16, x16, params16, Hn, None), np.float32) - oracle)))

    def old_mha(x, params):  # the pre-fix path: softmax in storage dtype
        from deeplearning4j_tpu.ops.math import precision_for
        q = _heads_split(jnp.dot(x, params["Wq"]), Hn)
        k = _heads_split(jnp.dot(x, params["Wk"]), Hn)
        v = _heads_split(jnp.dot(x, params["Wv"]), Hn)
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       precision=precision_for(q, k)) * scale
        att = jax.nn.softmax(s, axis=-1)
        y = jnp.einsum("bhqk,bhkd->bhqd", att, v,
                       precision=precision_for(att, v))
        return jnp.dot(_heads_join(y), params["Wo"])

    old_gap = float(np.max(np.abs(
        np.asarray(old_mha(x16, params16), np.float32) - oracle)))
    assert new_gap < old_gap, (new_gap, old_gap)


# ---------------------------------------------------------------------------
# SameDiff fusion pass
# ---------------------------------------------------------------------------

def _record_attention_chain(sd, name, q, k, v, mask_var, d, eps_add=False,
                            dropout_identity=False):
    """Record the exact op chain the TF importer emits for one BERT
    attention block (modelimport/tensorflow.py mappers)."""
    dk = sd.constant(f"{name}_dk", np.float32(np.sqrt(d)))
    scores = sd.call("linalg.mmul", q, k, name=f"{name}_scores",
                     attrs={"transpose_b": True})
    scaled = sd.call("math.div", scores, dk, name=f"{name}_scaled")
    masked = sd.call("math.add", scaled, mask_var, name=f"{name}_masked")
    if eps_add:  # HF stable_softmax: softmax(x + 1e-9)
        eps = sd.constant(f"{name}_eps", np.float32(1e-9))
        masked = sd.call("math.add", masked, eps, name=f"{name}_eps_add")
    probs = sd.call("act.softmax", masked, name=f"{name}_probs")
    if dropout_identity:  # frozen-graph dropout imports as identity
        probs = sd.call("act.identity", probs, name=f"{name}_drop")
    return sd.call("linalg.mmul", probs, v, name=f"{name}_ctx")


def test_fusion_pass_rewrites_imported_chain(rng):
    """Importer-shaped chain (incl. HF's +eps and the dropout identity):
    matched-site count asserted, graph outputs unchanged, fused op counted
    on dispatch, fused graph serializes and trains."""
    from deeplearning4j_tpu.autodiff import SameDiff, fuse_attention

    B, H, T, d = 2, 2, 16, 8
    sd = SameDiff()
    qv = sd.placeholder("q")
    kv = sd.placeholder("k")
    vv = sd.placeholder("v")
    mask = sd.constant("mask", ((rng.random((B, 1, 1, T)) > 0.25)
                                .astype(np.float32) - 1.0) * 10000.0)
    c1 = _record_attention_chain(sd, "a", qv, kv, vv, mask, d,
                                 eps_add=True, dropout_identity=True)
    c2 = _record_attention_chain(sd, "b", c1, kv, vv, mask, d)
    out = sd.call("math.mul", c2, sd._lift(2.0), name="out")

    feeds = {n: rng.normal(size=(B, H, T, d)).astype(np.float32)
             for n in "qkv"}
    before = sd.output(feeds, ["out"])["out"]
    rep = fuse_attention(sd)
    assert rep.matched == 2 and rep.unmatched == 0
    assert [r.op for r in sd._ops].count("attention.fused_sdpa") == 2
    assert "a_probs" not in sd._vars and "b_scores" not in sd._vars
    fa.reset_counters()
    after = sd.output(feeds, ["out"])["out"]
    np.testing.assert_allclose(after, before, atol=1e-5)
    # dispatch was consulted per fused site (reference fallback on CPU auto)
    c = fa.counters()
    assert sum(c.values()) >= 2

    # serde round-trip keeps the fused op
    import tempfile
    path = tempfile.mktemp(suffix=".zip")
    sd.save(path)
    from deeplearning4j_tpu.autodiff import SameDiff as SD2
    sd2 = SD2.load(path)
    np.testing.assert_allclose(sd2.output(feeds, ["out"])["out"], after,
                               atol=0)

    # trains through the fused op (custom VJP / reference autodiff)
    from deeplearning4j_tpu.nn.updaters import Sgd
    w = sd.var("w", rng.normal(size=(d, 1)).astype(np.float32))
    pred = sd.call("linalg.mmul", out, w, name="pred")
    sd.set_loss(pred.mean())
    sd.set_updater(Sgd(learning_rate=0.1))
    h = sd.fit(feeds, epochs=2)
    assert np.isfinite(h.losses).all()


def test_fusion_pass_prescaled_query_chain(rng):
    """Coverage-gap regression (r12): the PyTorch->ONNX export shape
    scales q BEFORE the scores mmul (q/sqrt(d) @ k^T). The pre-scale is
    absorbed into the fused op's scale and its q-sized elementwise op
    leaves the graph; outputs unchanged. A fan-out on the scaled q keeps
    the pre-scale un-absorbed (site still fuses with scale=1)."""
    from deeplearning4j_tpu.autodiff import SameDiff, fuse_attention

    B, H, T, d = 2, 2, 16, 8
    feeds = {n: rng.normal(size=(B, H, T, d)).astype(np.float32)
             for n in "qkv"}

    sd = SameDiff()
    q, k, v = (sd.placeholder(n) for n in "qkv")
    dk = sd.constant("dk", np.float32(np.sqrt(d)))
    q_scaled = sd.call("math.div", q, dk, name="q_scaled")
    scores = sd.call("linalg.mmul", q_scaled, k, name="scores",
                     attrs={"transpose_b": True})
    probs = sd.call("act.softmax", scores, name="probs")
    sd.call("linalg.mmul", probs, v, name="ctx")
    before = sd.output(feeds, ["ctx"])["ctx"]
    rep = fuse_attention(sd)
    assert rep.matched == 1 and rep.unmatched == 0
    assert "q_scaled" not in sd._vars  # the pre-scale op is gone
    fused = [r for r in sd._ops if r.op == "attention.fused_sdpa"]
    assert len(fused) == 1
    assert fused[0].attrs["scale"] == pytest.approx(1.0 / np.sqrt(d))
    assert fused[0].inputs[0] == "q"   # raw q feeds the fused op
    np.testing.assert_allclose(sd.output(feeds, ["ctx"])["ctx"], before,
                               atol=1e-5)

    # fan-out on the scaled q: the pre-scale must stay (it has another
    # consumer), the site fuses with scale 1.0 over the scaled input
    sd = SameDiff()
    q, k, v = (sd.placeholder(n) for n in "qkv")
    dk = sd.constant("dk", np.float32(np.sqrt(d)))
    q_scaled = sd.call("math.div", q, dk, name="q_scaled")
    scores = sd.call("linalg.mmul", q_scaled, k, name="scores",
                     attrs={"transpose_b": True})
    probs = sd.call("act.softmax", scores, name="probs")
    sd.call("linalg.mmul", probs, v, name="ctx")
    sd.call("reduce.sum", q_scaled, name="aux")  # second consumer
    before = sd.output(feeds, ["ctx"])["ctx"]
    rep = fuse_attention(sd)
    assert rep.matched == 1
    assert "q_scaled" in sd._vars
    fused = [r for r in sd._ops if r.op == "attention.fused_sdpa"]
    assert fused[0].attrs["scale"] == 1.0
    assert fused[0].inputs[0] == "q_scaled"
    np.testing.assert_allclose(sd.output(feeds, ["ctx"])["ctx"], before,
                               atol=1e-5)


def test_fusion_pass_safety_rules(rng):
    """Fan-out on an intermediate, a non-scalar scale, or a missing
    downstream mmul leave the graph UNTOUCHED (counted unmatched where the
    chain anchored a candidate)."""
    from deeplearning4j_tpu.autodiff import SameDiff, fuse_attention

    B, H, T, d = 1, 1, 8, 4
    feeds = {n: np.random.default_rng(0).normal(
        size=(B, H, T, d)).astype(np.float32) for n in "qkv"}

    # (1) probs consumed twice -> unmatched, graph unchanged
    sd = SameDiff()
    q, k, v = (sd.placeholder(n) for n in "qkv")
    scores = sd.call("linalg.mmul", q, k, attrs={"transpose_b": True})
    probs = sd.call("act.softmax", scores, name="probs")
    ctx = sd.call("linalg.mmul", probs, v, name="ctx")
    sd.call("reduce.sum", probs, name="extra")  # second consumer of probs
    n_ops = len(sd._ops)
    rep = fuse_attention(sd)
    assert rep.matched == 0 and rep.unmatched == 1
    assert len(sd._ops) == n_ops
    assert sd.output(feeds, ["ctx"])["ctx"].shape == (B, H, T, d)

    # (2) softmax feeding something that is not a plain mmul: not a site
    sd = SameDiff()
    q, k = sd.placeholder("q"), sd.placeholder("k")
    scores = sd.call("linalg.mmul", q, k, attrs={"transpose_b": True})
    probs = sd.call("act.softmax", scores)
    sd.call("reduce.sum", probs, attrs={"axis": -1})
    rep = fuse_attention(sd)
    assert rep.matched == 0 and rep.unmatched == 0

    # (3) tensor-valued "scale" operand -> unmatched by the const check
    sd = SameDiff()
    q, k, v = (sd.placeholder(n) for n in "qkv")
    t = sd.constant("t", np.ones((T, T), np.float32))
    scores = sd.call("linalg.mmul", q, k, attrs={"transpose_b": True})
    scaled = sd.call("math.mul", scores, t)
    probs = sd.call("act.softmax", scaled)
    sd.call("linalg.mmul", probs, v)
    rep = fuse_attention(sd)
    assert rep.matched == 0 and rep.unmatched == 1


@pytest.mark.slow
def test_fusion_minibert_graphdef_import():
    """End-to-end (ISSUE 3 acceptance): freeze a mini-BERT TF graph,
    import, fuse — matched-site count == n_layers, outputs equal."""
    tf = pytest.importorskip("tensorflow")
    transformers = pytest.importorskip("transformers")
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)

    from deeplearning4j_tpu.autodiff.fusion import fuse_attention
    from deeplearning4j_tpu.modelimport.tensorflow import (
        TensorflowFrameworkImporter)

    cfg = transformers.BertConfig(
        num_hidden_layers=2, hidden_size=64, num_attention_heads=2,
        intermediate_size=128, vocab_size=100, max_position_embeddings=64)
    m = transformers.TFBertModel(cfg)

    @tf.function
    def f(ids):
        return m(ids).last_hidden_state

    conc = f.get_concrete_function(tf.TensorSpec([2, 16], tf.int32))
    frozen = convert_variables_to_constants_v2(conc)
    iname = frozen.inputs[0].name.split(":")[0]
    oname = frozen.outputs[0].name.split(":")[0]
    sd = TensorflowFrameworkImporter.import_graph_def(
        frozen.graph.as_graph_def())
    ids = np.random.default_rng(0).integers(0, 100, (2, 16)).astype(np.int32)
    before = sd.output({iname: ids}, [oname])[oname]
    rep = fuse_attention(sd)
    assert rep.matched == 2, (rep.matched, rep.reasons)
    after = sd.output({iname: ids}, [oname])[oname]
    np.testing.assert_allclose(after, before, atol=1e-5)


@pytest.mark.parametrize("route,reference_key,sharded_key", [
    ("one_shot", "fallback_gspmd", "fallback_gspmd"),
    ("decode", "decode_fallback_gspmd", "decode_tp_shard_map"),
    ("multiquery", "decode_multiquery_fallback_gspmd",
     "decode_multiquery_tp_shard_map"),
])
def test_partitioned_trace_routes_around_the_kernel(
        rng, route, reference_key, sharded_key):
    """In a trace that GSPMD partitions (``pallas_kernels.gspmd_trace``)
    no dispatcher hands GSPMD a kernel, which the TPU compiler cannot
    partition: the reference path, counted, or for the decode kernels a
    shard_map over the model axis when the heads divide it. One flag, held
    per thread; a one-device mesh arms nothing."""
    import threading
    from jax.sharding import Mesh
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    tq = {"one_shot": 128, "decode": 1, "multiquery": 4}[route]
    q = jnp.asarray(rng.standard_normal((2, 4, tq, 64)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 4, 128, 64)), jnp.float32)
            for _ in range(2))
    lengths = jnp.asarray([100, 37], jnp.int32)
    call = {"one_shot": lambda: fa.attention(q, k, v),
            "decode": lambda: fa.decode_dispatch(q, k, v, lengths),
            "multiquery": lambda: fa.decode_multiquery_dispatch(
                q, k, v, lengths)}[route]
    fused_key = {"one_shot": "fused", "decode": "decode_fused",
                 "multiquery": "decode_multiquery"}[route]
    devs = np.array(jax.devices()[:2])
    old = fa.set_mode("force")
    try:
        fa.reset_counters()
        want = call()
        assert fa.counters()[fused_key] == 1
        with pk.gspmd_trace(Mesh(devs[:1], ("data",))):
            assert pk.partitioned() is None
        with pk.gspmd_trace(Mesh(devs, ("data",))):
            seen = []
            t = threading.Thread(target=lambda: seen.append(pk.partitioned()))
            t.start()
            t.join()
            assert seen == [None] and pk.partitioned() is not None
            got = call()
        c = fa.counters()
        assert (c[reference_key], c[fused_key]) == (1, 1)
        np.testing.assert_allclose(got, want, atol=2e-5)
        fa.reset_counters()
        with pk.gspmd_trace(Mesh(devs.reshape(1, 2), ("data", "model")),
                            "model"):
            got = call()
        assert pk.partitioned() is None
        c = fa.counters()
        assert c[sharded_key] == 1
        # inside the shard_map the kernel sees one device's heads
        assert c[fused_key] == (0 if route == "one_shot" else 1)
        np.testing.assert_allclose(got, want, atol=2e-5)
    finally:
        fa.set_mode(old)
