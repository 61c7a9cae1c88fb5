"""Fused-epilogue kernel library (ISSUE 16): epilogue-kernel VJP parity
(interpret mode on CPU — the REAL kernel code: affine+act with the
f32-scratch per-channel grad accumulator, LayerNorm+act with saved
mean/rstd), dispatch mode/counters (zero silent fallbacks, incl. the
fused master-cast updater decisions), every autotune candidate block,
the SameDiff ``fuse_epilogues`` rewrite pass (LN + exact-GeLU splice,
safety rules, serde, train-through), bit-parity of the fused
master-cast+updater step vs the unfused program (params AND updater
state, SameDiff and engine), the bf16 LSTM ``fits_vmem`` itemsize fix,
and the ``fusion-applied`` lint rules."""

import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import ops
from deeplearning4j_tpu.ops import autotune as at
from deeplearning4j_tpu.ops import fused_epilogues as fe
from deeplearning4j_tpu.ops import nnops


@pytest.fixture
def force_mode():
    """Route dispatch through the kernels (interpret off-TPU)."""
    old = fe.set_mode("force")
    fe.reset_counters()
    yield
    fe.set_mode(old)


def _assert_tree_bits_equal(a, b, what=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), what
    for x, y in zip(la, lb):
        ax, ay = np.asarray(x), np.asarray(y)
        assert ax.dtype == ay.dtype, (what, ax.dtype, ay.dtype)
        if ax.dtype.kind in "fV":  # float (incl. bf16 ext dtype): raw bits
            ax, ay = ax.view(np.uint8), ay.view(np.uint8)
        np.testing.assert_array_equal(ax, ay, err_msg=what)


def _ln_ref(x, g, b, eps, act):
    """The kernel's math, unfused: f32 LN + affine + catalog act."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    z = (x32 - mu) * jax.lax.rsqrt(var + eps) \
        * g.astype(jnp.float32) + b.astype(jnp.float32)
    return fe._act_fwd(act, z).astype(x.dtype)


def _affine_ref(x, s, b, act):
    x32 = x.astype(jnp.float32)
    z = x32 + b.astype(jnp.float32) if s is None \
        else x32 * s.astype(jnp.float32) + b.astype(jnp.float32)
    return fe._act_fwd(act, z).astype(x.dtype)


# ---------------------------------------------------------------------------
# epilogue VJP parity vs the unfused reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,ftol,gtol", [(np.float32, 1e-5, 1e-4),
                                             ("bfloat16", 2e-2, 1e-1)])
def test_bn_act_epilogue_parity(rng, force_mode, dtype, ftol, gtol):
    """bn_act routed through the kernel == the exact unfused layer pair
    (nnops.batch_norm + catalog act), forward AND grads to x/gamma/beta,
    ragged (zero-padded) tail rows included."""
    x = jnp.asarray(rng.normal(size=(6, 8, 128)), dtype)
    x = x.at[-1].set(0.0)  # padded tail rows ride the same kernel
    gamma = jnp.asarray(rng.normal(size=(128,)) + 1.0, jnp.float32)
    beta = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    mean = jnp.asarray(rng.normal(size=(128,)) * 0.1, jnp.float32)
    var = jnp.asarray(rng.random(128) + 0.5, jnp.float32)

    def ref(x, gamma, beta):
        y = nnops.batch_norm(x, gamma, beta, mean, var, 1e-5, -1)
        return fe.reference_act("relu")(y)

    out = fe.bn_act(x, gamma, beta, mean, var, 1e-5, act="relu")
    assert fe.counters()["fused"] >= 1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref(x, gamma, beta), np.float32),
                               atol=ftol, rtol=ftol)

    def loss(path, x, g, b):
        return jnp.sum(jnp.sin(path(x, g, b).astype(jnp.float32)))

    gf = jax.grad(lambda *a: loss(
        lambda x, g, b: fe.bn_act(x, g, b, mean, var, 1e-5, act="relu"),
        *a), argnums=(0, 1, 2))(x, gamma, beta)
    gr = jax.grad(lambda *a: loss(ref, *a), argnums=(0, 1, 2))(x, gamma,
                                                               beta)
    for got, want in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=gtol, rtol=gtol)


@pytest.mark.parametrize("act", ["gelu_exact", "gelu", "sigmoid"])
def test_bias_act_epilogue_parity(rng, force_mode, act):
    """bias_act kernel == broadcast-add + catalog activation, fwd + grads
    to x and the bias vector."""
    x = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(128,)), jnp.float32)

    def ref(x, b):
        return fe.reference_act(act)(x + b[None, :])

    out = fe.bias_act(x, b, act=act)
    assert fe.counters()["fused"] >= 1
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x, b)),
                               atol=1e-5)

    def loss(path, x, b):
        return jnp.sum(jnp.sin(path(x, b)))

    gf = jax.grad(lambda *a: loss(
        lambda x, b: fe.bias_act(x, b, act=act), *a), argnums=(0, 1))(x, b)
    gr = jax.grad(lambda *a: loss(ref, *a), argnums=(0, 1))(x, b)
    for got, want in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)
    ops.mark_fwd_tested("epilogue.bias_act")
    ops.mark_grad_tested("epilogue.bias_act")


@pytest.mark.parametrize("dtype,ftol,gtol", [(np.float32, 1e-5, 1e-4),
                                             ("bfloat16", 2e-2, 1e-1)])
def test_layer_norm_act_epilogue_parity(rng, force_mode, dtype, ftol, gtol):
    """layer_norm_act kernel == nnops.layer_norm + act, fwd + grads; the
    backward's masked-cotangent path (downstream loss masks ragged rows)
    matches autodiff through the reference."""
    x = jnp.asarray(rng.normal(size=(2, 16, 128)), dtype)
    g = jnp.asarray(rng.normal(size=(128,)) + 1.0, dtype)
    b = jnp.asarray(rng.normal(size=(128,)), dtype)
    rowmask = jnp.asarray(
        (np.arange(16) < 11).astype(np.float32))[None, :, None]

    def ref(x, g, b):
        y = nnops.layer_norm(x, g, b, 1e-5, axis=-1)
        return fe.reference_act("gelu")(y)

    out = fe.layer_norm_act(x, g, b, 1e-5, act="gelu")
    assert fe.counters()["fused"] >= 1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref(x, g, b), np.float32),
                               atol=ftol, rtol=ftol)

    def loss(path, x, g, b):  # ragged rows: cotangent zeroed on the tail
        return jnp.sum((path(x, g, b).astype(jnp.float32)) * rowmask)

    gf = jax.grad(lambda *a: loss(
        lambda x, g, b: fe.layer_norm_act(x, g, b, 1e-5, act="gelu"),
        *a), argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(lambda *a: loss(ref, *a), argnums=(0, 1, 2))(x, g, b)
    for got, want in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=gtol, rtol=gtol)
    ops.mark_fwd_tested("epilogue.layer_norm_act")
    ops.mark_grad_tested("epilogue.layer_norm_act")


@pytest.mark.parametrize("kind", ["affine", "ln"])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_every_autotune_candidate_parity(rng, kind, dtype):
    """EVERY feasible autotune row block runs the kernel (interpret) and
    matches the unfused f32 math, fwd + grads — a cached block from any
    sweep can never select a numerically different program."""
    rows, cols = 32, 128
    tol = 1e-5 if dtype == np.float32 else 2e-2
    gtol = 1e-4 if dtype == np.float32 else 1e-1
    cands = at.epilogue_candidates(kind, rows, cols, dtype)
    assert len(cands) >= 2, cands
    mult = fe._row_mult(dtype)
    assert all(b % mult == 0 and rows % b == 0 for b in cands)

    x = jnp.asarray(rng.normal(size=(rows, cols)), dtype)
    vdt = jnp.float32 if kind == "affine" else jnp.dtype(dtype)
    g = jnp.asarray(rng.normal(size=(1, cols)) + 1.0, vdt)
    b = jnp.asarray(rng.normal(size=(1, cols)), vdt)

    if kind == "ln":
        fused = lambda br: (lambda x, g, b: fe._ln_act(
            x, g, b, 1e-6, "gelu", br, True))
        ref = lambda x, g, b: _ln_ref(x, g[0], b[0], 1e-6, "gelu")
    else:
        fused = lambda br: (lambda x, g, b: fe._affine_act(
            x, g, b, "relu", br, True))
        ref = lambda x, g, b: _affine_ref(x, g[0], b[0], "relu")

    def loss(path, x, g, b):
        return jnp.sum(jnp.sin(path(x, g, b).astype(jnp.float32)))

    gr = jax.grad(lambda *a: loss(ref, *a), argnums=(0, 1, 2))(x, g, b)
    want = ref(x, g, b)
    for br in cands:
        got = fused(br)(x, g, b)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol,
                                   rtol=tol, err_msg=f"{kind} br={br}")
        gf = jax.grad(lambda *a: loss(fused(br), *a),
                      argnums=(0, 1, 2))(x, g, b)
        for gg, gw in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(gg, np.float32),
                                       np.asarray(gw, np.float32),
                                       atol=gtol, rtol=gtol,
                                       err_msg=f"{kind} br={br}")


def test_autotune_sweep_interpret_and_cache():
    """epilogue_sweep: CPU raises without interpret=True; the interpret
    sweep times every candidate, caches the winner (tagged for re-sweep),
    and epilogue_blocks resolves hit/default with counted events."""
    at.reset()
    at.reset_epilogue_counters()
    with pytest.raises(RuntimeError, match="TPU"):
        at.epilogue_sweep("affine", 32, 128, np.float32)
    entry = at.epilogue_sweep("affine", 32, 128, np.float32,
                              interpret=True, repeats=1)
    cands = at.epilogue_candidates("affine", 32, 128, np.float32)
    assert entry["source"] == "sweep_interpret"
    assert len(entry["candidates"]) == len(cands)
    assert entry["blocks"][0] in cands
    c = at.epilogue_counters()
    assert c["sweep"] == 1 and c["sweep_candidate"] == len(cands)
    # cached winner resolves as a hit
    br = at.epilogue_blocks("affine", 32, 128, np.float32)
    assert br == entry["blocks"][0]
    assert at.epilogue_counters()["hit"] == 1
    # fresh key on CPU: seeded default (never sweeps inline), counted
    br2 = at.epilogue_blocks("ln", 64, 128, np.float32)
    assert br2 == fe.row_block(64, 8)
    assert at.epilogue_counters()["default"] == 1
    at.reset()


# ---------------------------------------------------------------------------
# dispatch: modes + zero-silent-fallback counters
# ---------------------------------------------------------------------------

def test_dispatch_fallbacks_and_counters(rng):
    """Every fallback reproduces the EXACT unfused formula with a counter
    bump; every decision (kernel and updater) lands in exactly one
    counter."""
    x = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)
    g = jnp.ones((128,), jnp.float32)
    b = jnp.zeros((128,), jnp.float32)
    mean = jnp.zeros((128,), jnp.float32)
    var = jnp.ones((128,), jnp.float32)

    old = fe.set_mode("off")
    fe.reset_counters()
    try:
        # off -> reference path, bit-identical to the unfused layer pair
        y = fe.bn_act(x, g, b, mean, var, 1e-5, act="relu")
        np.testing.assert_array_equal(
            np.asarray(y),
            np.asarray(fe.reference_act("relu")(
                nnops.batch_norm(x, g, b, mean, var, 1e-5, -1))))
        assert fe.counters()["fallback_mode"] == 1
        # fused updater disabled in off mode
        assert fe.dispatch_updater("BFLOAT16") == "fallback_updater_mode"

        fe.set_mode("auto")  # CPU: platform fallback, still exact
        y = fe.layer_norm_act(x, g, b, 1e-5, act="gelu")
        np.testing.assert_array_equal(
            np.asarray(y),
            np.asarray(fe.reference_act("gelu")(
                nnops.layer_norm(x, g, b, 1e-5, axis=-1))))
        assert fe.counters()["fallback_platform"] == 1

        fe.set_mode("force")
        # parameterized activation (alpha) -> fallback_act
        fe.bias_act(x, b, act="leakyrelu", alpha=0.2)
        assert fe.counters()["fallback_act"] == 1
        # int dtype -> fallback_dtype
        fe.bias_act(x.astype(jnp.int32), b.astype(jnp.int32), act="relu")
        assert fe.counters()["fallback_dtype"] == 1
        # rank-1 input / non-last axis -> fallback_shape
        fe.bias_act(x[0], b, act="relu")
        v16 = jnp.ones((16,), jnp.float32)
        fe.bn_act(x, v16, v16, v16 * 0.0, v16, 1e-5, axis=0, act="relu")
        assert fe.counters()["fallback_shape"] == 2
        # per-step VMEM overflow -> fallback_vmem
        big = jnp.zeros((8, 65536), jnp.float32)
        fe.bias_act(big, jnp.zeros((65536,), jnp.float32), act="relu")
        assert fe.counters()["fallback_vmem"] == 1
        # fused route under force, counted
        before = fe.counters()["fused"]
        fe.bias_act(x, b, act="relu")
        assert fe.counters()["fused"] == before + 1

        # updater routing: fused under a mixed policy, attributed
        # fallbacks for f32 and penalty-bearing engine steps
        assert fe.dispatch_updater("BFLOAT16") is None
        assert fe.counters()["fused_updater"] == 1
        assert fe.dispatch_updater("FLOAT") == "fallback_updater_dtype"
        assert fe.dispatch_updater(
            "BFLOAT16", has_penalty=True) == "fallback_updater_penalty"
        c = fe.counters()
        assert c["fallback_updater_dtype"] == 1
        assert c["fallback_updater_penalty"] == 1
        # zero silent decisions: every call above is attributed
        assert sum(c.values()) == 12, c
        # forced kernels and a CPU's auto never read the TPU-auto key
        assert c["fallback_conv_layout"] == 0
    finally:
        fe.set_mode(old)
    with pytest.raises(ValueError, match="mode"):
        fe.set_mode("sometimes")


def test_engine_bn_act_fold_plan_and_output_parity(rng):
    """The MLN fold plan folds a following ActivationLayer into the BN
    epilogue; auto-on-CPU output is BIT-identical to off (the fallback is
    the exact unfused formula) and force (interpret kernel) matches."""
    from deeplearning4j_tpu.nn.config import InputType, \
        NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers.conv import BatchNormalization, \
        ConvolutionLayer
    from deeplearning4j_tpu.nn.layers.core import ActivationLayer, \
        OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Sgd

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Sgd(learning_rate=0.05))
            .input_type(InputType.convolutional(3, 8, 8,
                                                data_format="NHWC"))
            .list(ConvolutionLayer(n_out=8, kernel=(3, 3), mode="same",
                                   activation="identity",
                                   data_format="NHWC"),
                  BatchNormalization(data_format="NHWC"),
                  ActivationLayer(activation="relu"),
                  OutputLayer(n_out=3))
            .build())
    m = MultiLayerNetwork(conf).init()
    fold, skip = m._epilogue_fold_plan()
    assert fold == {1: "relu"} and skip == frozenset({2})

    x = jnp.asarray(rng.normal(size=(4, 8, 8, 3)), jnp.float32)

    def fwd():  # eager layer walk: dispatch decided fresh per call
        return np.asarray(m._forward(m.params, x, m.state, train=False,
                                     rng=None)[0])

    old = fe.set_mode("off")
    try:
        y_off = fwd()
        fe.set_mode("auto")
        fe.reset_counters()
        y_auto = fwd()
        assert fe.counters()["fallback_platform"] >= 1
        np.testing.assert_array_equal(y_auto, y_off)
        fe.set_mode("force")
        fe.reset_counters()
        y_force = fwd()
        assert fe.counters()["fused"] >= 1
        np.testing.assert_allclose(y_force, y_off, atol=5e-4)
    finally:
        fe.set_mode(old)


# ---------------------------------------------------------------------------
# SameDiff fuse_epilogues rewrite pass
# ---------------------------------------------------------------------------

def _record_ln_chain(sd, x, prefix, C, rng, form="keras"):
    """The two TF-importer spellings of LayerNorm the matcher handles."""
    g = sd.var(f"{prefix}_gamma",
               (rng.normal(size=(C,)) + 1.0).astype(np.float32))
    b = sd.var(f"{prefix}_beta", rng.normal(size=(C,)).astype(np.float32))
    eps = sd.constant(f"{prefix}_eps", np.float32(1e-5))
    mean = sd.call("reduce.mean", x, axis=(-1,), keepdims=True)
    if form == "keras":  # keras-folded: x*inv2 + (beta - mean*inv2)
        sqd = sd.call("math.squared_difference", x, mean)
        var = sd.call("reduce.mean", sqd, axis=(-1,), keepdims=True)
        inv = sd.call("math.rsqrt", sd.call("math.add", var, eps))
        inv2 = sd.call("math.mul", inv, g)
        t1 = sd.call("math.mul", x, inv2)
        t2 = sd.call("math.mul", mean, inv2)
        s = sd.call("math.sub", b, t2)
        return sd.call("math.add", t1, s, name=f"{prefix}_out")
    d = sd.call("math.sub", x, mean)  # plain: ((x-mean)*inv)*gamma + beta
    sq = sd.call("math.square", d)
    var = sd.call("reduce.mean", sq, axis=(-1,), keepdims=True)
    inv = sd.call("math.rsqrt", sd.call("math.add", var, eps))
    n = sd.call("math.mul", inv, d)
    gm = sd.call("math.mul", n, g)
    return sd.call("math.add", gm, b, name=f"{prefix}_out")


def _record_gelu_chain(sd, x, prefix, C, rng, grouping="a", bias=False):
    """Exact-GeLU (erf) as ONNX/TF exporters spell it, 3 mul groupings."""
    if bias:
        bv = sd.var(f"{prefix}_bias",
                    rng.normal(size=(C,)).astype(np.float32))
        x = sd.call("math.add", x, bv)
    c = sd.constant(f"{prefix}_c", np.float32(0.7071067811865476))
    one = sd.constant(f"{prefix}_one", np.float32(1.0))
    half = sd.constant(f"{prefix}_half", np.float32(0.5))
    e = sd.call("math.erf", sd.call("math.mul", x, c))
    f = sd.call("math.add", one, e)
    if grouping == "a":    # (x*f)*0.5
        return sd.call("math.mul", sd.call("math.mul", x, f), half,
                       name=f"{prefix}_out")
    if grouping == "b":    # (0.5*f)*x
        return sd.call("math.mul", sd.call("math.mul", half, f), x,
                       name=f"{prefix}_out")
    return sd.call("math.mul", f, sd.call("math.mul", half, x),
                   name=f"{prefix}_out")  # f*(0.5*x)


@pytest.mark.parametrize("form", ["keras", "plain"])
def test_fusion_pass_rewrites_ln_chain(rng, form):
    """Both importer LN spellings splice to epilogue.layer_norm_act:
    outputs unchanged, the decomposition's intermediates leave the graph,
    the final output name survives, dispatch is consulted."""
    from deeplearning4j_tpu.autodiff.fusion import fuse_epilogues
    from deeplearning4j_tpu.autodiff.samediff import SameDiff

    C = 16
    sd = SameDiff.create()
    x = sd.placeholder("x", (None, C))
    out = _record_ln_chain(sd, x, "ln", C, rng, form=form)
    X = rng.normal(size=(16, C)).astype(np.float32)
    before = sd.output({"x": X}, [out.name])[out.name]
    n_ops = len(sd._ops)
    rep = fuse_epilogues(sd)
    assert rep.matched == 1 and rep.unmatched == 0, rep.reasons
    assert rep.kinds == ["layer_norm"]
    fused = [r for r in sd._ops if r.op == "epilogue.layer_norm_act"]
    assert len(fused) == 1
    assert fused[0].output == out.name  # splice keeps the output name
    assert fused[0].attrs["eps"] == pytest.approx(1e-5)
    assert len(sd._ops) < n_ops  # the decomposition actually shrank
    fe.reset_counters()
    after = sd.output({"x": X}, [out.name])[out.name]
    np.testing.assert_allclose(after, before, atol=1e-5)
    assert sum(fe.counters().values()) >= 1  # dispatch consulted

    # force mode routes the spliced op through the interpret kernel
    old = fe.set_mode("force")
    try:
        sd._fn_cache.clear()
        fe.reset_counters()
        y_force = sd.output({"x": X}, [out.name])[out.name]
        assert fe.counters()["fused"] >= 1
        np.testing.assert_allclose(y_force, before, atol=1e-4)
    finally:
        fe.set_mode(old)
        sd._fn_cache.clear()


@pytest.mark.parametrize("grouping", ["a", "b", "c"])
@pytest.mark.parametrize("bias", [False, True])
def test_fusion_pass_rewrites_gelu_chain(rng, grouping, bias):
    """All three exporter mul-groupings of exact GeLU splice to
    epilogue.bias_act(act=gelu_exact); a rank-1 upstream bias-add is
    absorbed into the fused op when safe."""
    from deeplearning4j_tpu.autodiff.fusion import fuse_epilogues
    from deeplearning4j_tpu.autodiff.samediff import SameDiff

    C = 16
    sd = SameDiff.create()
    x = sd.placeholder("x", (None, C))
    out = _record_gelu_chain(sd, x, "g", C, rng, grouping=grouping,
                             bias=bias)
    X = rng.normal(size=(8, C)).astype(np.float32)
    before = sd.output({"x": X}, [out.name])[out.name]
    rep = fuse_epilogues(sd)
    assert rep.matched == 1 and rep.unmatched == 0, rep.reasons
    assert rep.kinds == ["gelu"]
    fused = [r for r in sd._ops if r.op == "epilogue.bias_act"]
    assert len(fused) == 1
    assert fused[0].attrs["act"] == "gelu_exact"
    assert len(fused[0].inputs) == (2 if bias else 1)
    after = sd.output({"x": X}, [out.name])[out.name]
    np.testing.assert_allclose(after, before, atol=2e-6)


def test_fusion_pass_serde_and_train_through(rng):
    """A fused graph serde round-trips (op name + attrs survive save/load)
    and trains THROUGH the spliced epilogue op (reference autodiff under
    auto-on-CPU; the op resolves via the registry like any catalog op)."""
    from deeplearning4j_tpu.autodiff.fusion import fuse_epilogues
    from deeplearning4j_tpu.autodiff.samediff import SameDiff
    from deeplearning4j_tpu.nn.updaters import Sgd

    C = 16
    sd = SameDiff.create()
    x = sd.placeholder("x", (None, C))
    ln = _record_ln_chain(sd, x, "ln", C, rng, form="keras")
    out = _record_gelu_chain(sd, ln, "g", C, rng, grouping="a")
    X = rng.normal(size=(8, C)).astype(np.float32)
    rep = fuse_epilogues(sd)
    assert rep.matched == 2 and sorted(rep.kinds) == ["gelu", "layer_norm"]
    after = sd.output({"x": X}, [out.name])[out.name]

    path = tempfile.mktemp(suffix=".zip")
    sd.save(path)
    sd2 = SameDiff.load(path)
    assert [r.op for r in sd2._ops].count("epilogue.layer_norm_act") == 1
    assert [r.op for r in sd2._ops].count("epilogue.bias_act") == 1
    np.testing.assert_allclose(sd2.output({"x": X}, [out.name])[out.name],
                               after, atol=0)

    w = sd.var("w", rng.normal(size=(C, 1)).astype(np.float32))
    pred = sd.call("linalg.mmul", out, w, name="pred")
    sd.set_loss(pred.mean())
    sd.set_updater(Sgd(learning_rate=0.05))
    h = sd.fit([{"x": X}], epochs=3)
    assert np.isfinite(h.losses).all()


def test_fusion_pass_safety_rules(rng):
    """An intermediate with a consumer OUTSIDE the candidate chain leaves
    the graph untouched (unmatched + reason); a graph with no anchors
    reports nothing."""
    from deeplearning4j_tpu.autodiff.fusion import fuse_epilogues
    from deeplearning4j_tpu.autodiff.samediff import SameDiff

    C = 16
    sd = SameDiff.create()
    x = sd.placeholder("x", (None, C))
    out = _record_ln_chain(sd, x, "ln", C, rng, form="keras")
    # second consumer of the mean intermediate -> removal would change it
    mean_name = next(r.output for r in sd._ops if r.op == "reduce.mean")
    sd.call("math.square", sd._vars[mean_name], name="outside_sq")
    X = rng.normal(size=(8, C)).astype(np.float32)
    before = sd.output({"x": X}, [out.name, "outside_sq"])
    n_ops = len(sd._ops)
    rep = fuse_epilogues(sd)
    assert rep.matched == 0 and rep.unmatched == 1
    assert any("consumer" in r or "outside" in r for r in rep.reasons), \
        rep.reasons
    assert len(sd._ops) == n_ops  # untouched
    after = sd.output({"x": X}, [out.name, "outside_sq"])
    for k in before:
        np.testing.assert_array_equal(after[k], before[k])

    # no anchors: clean no-op report
    sd2 = SameDiff.create()
    a = sd2.placeholder("a")
    sd2.call("math.mul", a, a, name="sq")
    rep2 = fuse_epilogues(sd2)
    assert rep2.matched == 0 and rep2.unmatched == 0


# ---------------------------------------------------------------------------
# fused master-cast + updater: bit-parity vs the unfused program
# ---------------------------------------------------------------------------

def _sd_mlp(seed=0):
    from deeplearning4j_tpu.autodiff.samediff import SameDiff
    from deeplearning4j_tpu.nn.updaters import Adam

    rng = np.random.default_rng(seed)
    sd = SameDiff.create()
    x = sd.placeholder("x")
    y = sd.placeholder("y")
    w1 = sd.var("w1", rng.normal(0, 0.4, (8, 16)).astype(np.float32))
    b1 = sd.var("b1", np.zeros(16, np.float32))
    w2 = sd.var("w2", rng.normal(0, 0.4, (16, 3)).astype(np.float32))
    b2 = sd.var("b2", np.zeros(3, np.float32))
    h = sd.call("act.tanh", x.mmul(w1) + b1)
    logits = h.mmul(w2) + b2
    sd.set_loss(sd.call("loss.softmax_ce_logits", y, logits))
    sd.set_updater(Adam(learning_rate=1e-2))
    sd.set_dtype("BFLOAT16")
    return sd


def _run_sd_steps(sd, feeds_list, n_steps):
    """Drive the compiled fit step manually (pre-sentinel signature) so
    the updater state is observable; returns (masters, opt_state,
    losses)."""
    from deeplearning4j_tpu.autodiff.samediff import VARIABLE

    train_names = [k for k, v in sd._vars.items() if v.kind == VARIABLE]
    tv = {k: sd._values[k] for k in train_names}
    opt = sd.updater.init_state(tv)
    carry = sd._fit_carry(tv)
    step = sd._fit_step_cached()
    losses = []
    for i in range(n_steps):
        feeds = {k: jnp.asarray(v)
                 for k, v in feeds_list[i % len(feeds_list)].items()}
        carry, opt, loss = step(carry, opt, {},
                                jnp.asarray(i, jnp.int32), feeds)
        losses.append(float(loss))
    return sd._carry_masters(carry), opt, losses


def test_fused_updater_bit_parity_samediff(rng):
    """ISSUE 16 acceptance: the fused master-cast+updater SameDiff step
    is BIT-identical to the unfused step — params, updater state, and
    losses — with the fused/plain decision visible in the step spec."""
    feeds = [{"x": rng.normal(size=(32, 8)).astype(np.float32),
              "y": np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)]}
             for _ in range(3)]

    old = fe.set_mode("auto")
    try:
        sd_f = _sd_mlp()
        assert sd_f.fused_updater_active()
        tv_f, opt_f, loss_f = _run_sd_steps(sd_f, feeds, 6)
        assert sd_f._fn_cache["__fit_step__"][0][8] == "fused_cast"

        fe.set_mode("off")
        sd_u = _sd_mlp()
        assert not sd_u.fused_updater_active()
        tv_u, opt_u, loss_u = _run_sd_steps(sd_u, feeds, 6)
        assert sd_u._fn_cache["__fit_step__"][0][8] == "plain"
    finally:
        fe.set_mode(old)

    for k in tv_u:
        assert tv_f[k].dtype == jnp.float32  # masters stayed f32
    _assert_tree_bits_equal(tv_f, tv_u, "masters")
    _assert_tree_bits_equal(opt_f, opt_u, "updater state")
    np.testing.assert_array_equal(np.asarray(loss_f, np.float32),
                                  np.asarray(loss_u, np.float32))


def test_fused_updater_bit_parity_engine(rng):
    """Engine acceptance: MultiLayerNetwork under the bf16 policy trains
    bit-identically with the fused step (auto) and the unfused step
    (off) — params AND updater state — and an l1/l2 penalty keeps the
    unfused split (the loss must read f32 masters)."""
    from deeplearning4j_tpu.nn.config import InputType, \
        NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam

    def mln(l2=0.0):
        b = (NeuralNetConfiguration.builder().seed(7)
             .data_type("BFLOAT16").updater(Adam(learning_rate=1e-2))
             .input_type(InputType.feed_forward(12)))
        if l2:
            b = b.l2(l2)
        conf = b.list(DenseLayer(n_out=16, activation="tanh"),
                      OutputLayer(n_out=3, loss="mcxent",
                                  activation="softmax")).build()
        return MultiLayerNetwork(conf).init()

    x = rng.normal(size=(32, 12)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)]

    old = fe.set_mode("auto")
    try:
        m_f = mln()
        assert m_f.fused_updater_active()
        assert not mln(l2=1e-4).fused_updater_active()  # penalty splits
        m_f.fit(x, y, epochs=3)

        fe.set_mode("off")
        m_u = mln()
        assert not m_u.fused_updater_active()
        m_u.fit(x, y, epochs=3)
    finally:
        fe.set_mode(old)

    for leaf in jax.tree.leaves(m_f.params):
        assert leaf.dtype == jnp.float32
    _assert_tree_bits_equal(m_f.params, m_u.params, "params")
    _assert_tree_bits_equal(m_f.updater_state, m_u.updater_state,
                            "updater state")


# ---------------------------------------------------------------------------
# bf16 LSTM Pallas-cell VMEM fit (satellite: itemsize plumb fix)
# ---------------------------------------------------------------------------

def test_lstm_bf16_vmem_fit_dispatches_fused(rng, monkeypatch):
    """Regression (ISSUE 16 satellite): the LSTM streaming path now hands
    ``fits_vmem`` the INPUT dtype's itemsize — a bf16 problem that fits
    at 2 bytes/element but not at 4 dispatches the fused cell instead of
    silently falling back to the lax cell."""
    from deeplearning4j_tpu.nn.layers.recurrent import LSTM
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    B, nin, u, T = 512, 384, 384, 2
    assert pk.fits_vmem(B, nin, u, 2)       # bf16 fits...
    assert not pk.fits_vmem(B, nin, u, 4)   # ...f32 does not

    calls = []

    def recording_cell(x_t, h, c, w, rw, b, forget_bias=1.0):
        calls.append(x_t.dtype)
        return nnops.lstm_cell(x_t, h, c, w, rw, b,
                               forget_bias=forget_bias)

    monkeypatch.setattr(pk, "available", lambda: True)
    monkeypatch.setattr(pk, "lstm_cell_fused", recording_cell)

    lyr = LSTM(n_out=u, n_in=nin, use_pallas_cell=True)
    for dtype, expect_fused in ((jnp.bfloat16, True), (jnp.float32, False)):
        params, _, _ = lyr.initialize(jax.random.PRNGKey(0), (T, nin),
                                      dtype)
        x = jnp.asarray(rng.normal(size=(B, T, nin)), dtype)
        carry = lyr.init_stream_state(params, B)
        calls.clear()
        y, _ = lyr.scan_with_state(params, x, carry, grad_path=False)
        assert np.isfinite(np.asarray(y, np.float32)).all()
        assert bool(calls) is expect_fused, (dtype, calls)


# ---------------------------------------------------------------------------
# fusion-applied lint rules (staticcheck)
# ---------------------------------------------------------------------------

def test_fusion_probe_green():
    """The lint gate's fusion probe traces the REAL fused bf16 conv/BN
    train step under force mode and must find zero silent fallbacks."""
    from deeplearning4j_tpu.runtime import staticcheck as sc

    assert sc.fusion_probe() == []


def test_fusion_rules_fire_on_unfused_step():
    """Negative: with the library off, the same audit flags BOTH silent
    gaps — no pallas_call in the program (epilogue rule) and a top-level
    f32->16-bit master-cast sweep (updater rule)."""
    from deeplearning4j_tpu.nn.config import InputType, \
        NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers.conv import BatchNormalization, \
        ConvolutionLayer
    from deeplearning4j_tpu.nn.layers.core import ActivationLayer, \
        OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Sgd
    from deeplearning4j_tpu.runtime import staticcheck as sc

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Sgd(learning_rate=0.05)).data_type("BFLOAT16")
            .input_type(InputType.convolutional(3, 8, 8,
                                                data_format="NHWC"))
            .list(ConvolutionLayer(n_out=8, kernel=(3, 3), mode="same",
                                   activation="identity",
                                   data_format="NHWC"),
                  BatchNormalization(data_format="NHWC"),
                  ActivationLayer(activation="relu"),
                  OutputLayer(n_out=3))
            .build())
    m = MultiLayerNetwork(conf).init()
    old = fe.set_mode("off")
    try:
        step = m._build_train_step()  # unfused signature under off
        avals = (jax.eval_shape(lambda: m.params),
                 jax.eval_shape(lambda: m.updater_state),
                 jax.eval_shape(lambda: m.state),
                 jax.ShapeDtypeStruct((), np.int32),
                 jax.eval_shape(lambda: jax.random.PRNGKey(0)),
                 jax.ShapeDtypeStruct((4, 8, 8, 3), np.float32),
                 jax.ShapeDtypeStruct((4, 3), np.float32), None, None)
        findings = sc.jaxpr_audit(
            step, avals, rules=(), expect_fusion=True,
            param_shapes=[tuple(l.shape)
                          for l in jax.tree.leaves(m.params)],
            policy="BFLOAT16", label="<test-unfused>")
    finally:
        fe.set_mode(old)
    rules = {f.rule for f in findings}
    assert "fusion-applied-epilogue" in rules, rules
    assert "fusion-applied-updater" in rules, rules


def test_partitioned_trace_routes_to_the_reference():
    """A Mosaic kernel cannot be partitioned by GSPMD: while a program
    partitioned over a mesh is traced (``pallas_kernels.gspmd_trace``) the
    epilogue kernels give way to the reference, counted; a one-device
    mesh partitions nothing."""
    from jax.sharding import Mesh
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    shape, dt = (64, 128), jnp.bfloat16
    devs = np.array(jax.devices()[:2])
    old = fe.set_mode("force")
    try:
        assert fe.route_elementwise(shape, dt) is None
        with pk.gspmd_trace(Mesh(devs[:1], ("data",))):
            assert fe.route_elementwise(shape, dt) is None
        with pk.gspmd_trace(Mesh(devs, ("data",))):
            assert fe.route_elementwise(shape, dt) == "fallback_gspmd"
            fe.reset_counters()
            x = jnp.ones(shape, dt)
            y = fe.bias_act(x, jnp.ones((128,), dt), act="relu")
            assert fe.counters()["fallback_gspmd"] == 1
            assert float(y[0, 0]) == 2.0
        assert pk.partitioned() is None
        assert fe.route_elementwise(shape, dt) is None
    finally:
        fe.set_mode(old)


# ---------------------------------------------------------------------------
# auto on a TPU: conv feature maps take XLA's epilogue (fallback_conv_layout)
# ---------------------------------------------------------------------------

@pytest.fixture
def on_the_chip(monkeypatch):
    """The dispatcher asks the backend, which is the CPU here: steer it
    onto its TPU branch, in ``auto``."""
    monkeypatch.setattr(fe, "_tpu_available", lambda: True)
    old = fe.set_mode("auto")
    fe.reset_counters()
    yield
    fe.set_mode(old)


@pytest.mark.parametrize("shape,kind,mode,want", [
    # a convolution's feature map, lane-aligned or not: XLA's own epilogue
    ((128, 56, 56, 256), "affine", "auto", "fallback_conv_layout"),
    ((128, 112, 112, 64), "affine", "auto", "fallback_conv_layout"),
    # rank 2 and 3 keep the decision they had
    ((4096, 768), "affine", "auto", None),
    ((4096, 768), "ln", "auto", None),
    ((32, 512, 768), "affine", "auto", None),
    ((32, 512, 768), "ln", "auto", None),
    ((32, 512, 3072), "affine", "auto", "fallback_vmem"),
    ((32, 512, 3072), "ln", "auto", "fallback_vmem"),
    # the LayerNorm kernels are not a conv epilogue at any rank
    ((8, 16, 16, 128), "ln", "auto", None),
    # force: kernels wherever they can run
    ((128, 56, 56, 256), "affine", "force", None),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_route_on_the_chip(on_the_chip, shape, kind, mode, want):
    fe.set_mode(mode)
    assert fe.route_elementwise(shape, jnp.bfloat16, kind=kind) == want


def test_route_conv_layout_gives_way_to_earlier_checks(on_the_chip):
    """A mesh trace still reads ``fallback_gspmd``, a channel-first map
    ``fallback_shape``, an activation without a kernel ``fallback_act``."""
    from jax.sharding import Mesh
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    shape, dt = (128, 56, 56, 256), jnp.bfloat16
    with pk.gspmd_trace(Mesh(np.array(jax.devices()[:2]), ("data",))):
        assert fe.route_elementwise(shape, dt) == "fallback_gspmd"
    assert fe.route_elementwise(shape, dt, axis=1) == "fallback_shape"
    assert fe.route_elementwise(shape, dt, act="leakyrelu",
                                alpha=0.2) == "fallback_act"


@pytest.mark.parametrize("op", ["bn_act", "bn_act_no_affine", "bias_act"])
def test_conv_layout_fallback_is_the_unfused_pair(rng, on_the_chip, op):
    """On a feature map in ``auto`` on a TPU the epilogue IS the legacy
    formula, bit for bit, counted once under the new key."""
    x = jnp.asarray(rng.normal(size=(2, 4, 4, 128)), jnp.bfloat16)
    gamma = jnp.asarray(rng.normal(size=(128,)) + 1.0, jnp.bfloat16)
    beta = jnp.asarray(rng.normal(size=(128,)), jnp.bfloat16)
    mean = jnp.asarray(rng.normal(size=(128,)) * 0.1, jnp.bfloat16)
    var = jnp.asarray(rng.random(128) + 0.5, jnp.bfloat16)
    relu = fe.reference_act("relu")
    if op == "bias_act":
        got = fe.bias_act(x, beta, act="relu")
        want = relu(x + beta.reshape(1, 1, 1, 128))
    else:
        g, b = (None, None) if op == "bn_act_no_affine" else (gamma, beta)
        got = fe.bn_act(x, g, b, mean, var, 1e-5, act="relu")
        want = relu(nnops.batch_norm(x, g, b, mean, var, 1e-5, -1))
    _assert_tree_bits_equal(got, want, op)
    c = fe.counters()
    assert c["fallback_conv_layout"] == 1 and sum(c.values()) == 1, c
