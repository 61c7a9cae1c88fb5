"""The Qwen3-Next stack (Gated DeltaNet layers three to one with gated full
attention, softmax-routed SwiGLU experts beside a gated shared expert)
against its plain reference, at tiny widths on the CPU in float32 with
seeded weights: the gated delta rule's chunked XLA path and its Pallas
kernels in interpret mode against the recurrence one position at a time,
forward and every gradient, over several chunks and at decays near 0 and
near 1; the whole stack's loss and every gradient leaf; the share test (the
shares of 16 experts of 64 add up to the uncut layer, the gated shared
expert counted once); the shared gate's default leaving the expert layer as
it was; the counters; the builder's refusals; and what must NOT pass (the
reference in bfloat16)."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3_next_80b_a3b as ref
from deeplearning4j_tpu.models.qwen3_next import qwen3_next
from deeplearning4j_tpu.nn.layers.decoder import (GatedDeltaNetLayer,
                                                  SparseExpertLayer)
from deeplearning4j_tpu.ops import causal_attention as ca
from deeplearning4j_tpu.ops import delta_rule as dr
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.runtime import telemetry as tel

program = importlib.import_module(
    "benchmarks.configs.qwen3_next_80b_a3b.program")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, B = 80, 2

#: float32 on both sides, the chunked form summing in another order than
#: the recurrence: the largest gap read over the regimes below is 5.4e-6
#: (the decay's gradient); 1e-4 leaves 18x of room, and the recurrence on
#: bfloat16-rounded inputs reads 1e-3 and more (test below)
RULE_TOL = 1e-4


def recurrent(q, k, v, g, beta):
    """The gated delta rule as defined, one position at a time."""
    Bq, Tq, H, dk = q.shape

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[..., None, None] * S
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
        S = S + jnp.einsum("bhk,bhv->bhkv", kt, u)
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((Bq, H, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


DECAYS = {"near_one": (-1e-3, 1e-3), "near_zero": (-12.0, 2.0),
          "mixed": (-1.0, 1.5)}


def rule_inputs(decay, seed=0, Tq=200, H=2, d=128):
    """Normed keys and queries as the layer makes them, ``g = lo * e^(s
    N)`` with ``(lo, s)`` the regime's: decays ``e^g`` near 1 or near 0."""
    rng = np.random.default_rng(seed)
    lo, s = DECAYS[decay]
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    return (f32(unit(rng.normal(size=(1, Tq, H, d))) / np.sqrt(d)),
            f32(unit(rng.normal(size=(1, Tq, H, d)))),
            f32(rng.normal(size=(1, Tq, H, d))),
            f32(lo * np.exp(s * rng.normal(size=(1, Tq, H)))),
            f32(1 / (1 + np.exp(-rng.normal(size=(1, Tq, H))))),
            f32(rng.normal(size=(1, Tq, H, d))))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture
def mode():
    old = fa.mode()
    yield fa.set_mode
    fa.set_mode(old)


def _rule_and_grads(fn, q, k, v, g, beta, w):
    loss = lambda *a: jnp.sum(fn(*a) * w)
    with jax.default_matmul_precision("highest"):
        return fn(q, k, v, g, beta), jax.grad(loss, argnums=range(5))(
            q, k, v, g, beta)


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("path", ["off", "force"], ids=["xla", "kernels"])
def test_the_chunked_rule_is_the_recurrence(mode, path, decay):
    """200 positions: three whole chunks of 64 and one padded; the XLA
    scan (``off``) and the kernels in interpret mode (``force``), forward
    and the gradients of ``q``, ``k``, ``v``, ``g`` and ``beta``."""
    *args, w = rule_inputs(decay)
    want, gwant = _rule_and_grads(recurrent, *args, w)
    mode(path)
    counter = tel.registry.get("linear_attn.dispatch")
    labels = {"decision": "kernel"} if path == "force" else \
        {"decision": "xla", "why": "mode"}
    before = counter.value(**labels)
    got, ggot = _rule_and_grads(dr.gated_delta_rule, *args, w)
    assert counter.value(**labels) > before
    assert rel(got, want) < RULE_TOL
    for name, a, b in zip("qkvgb", ggot, gwant):
        assert rel(a, b) < RULE_TOL, name


def test_the_kernels_are_the_xla_scan_in_bfloat16(mode):
    """The program's dtype: the kernels (interpret mode) and the XLA twin
    run the same per-chunk steps on bfloat16 operands; they agree to
    float32 rounding, forward and backward."""
    *args, w = rule_inputs("mixed", seed=3, Tq=128, H=4)
    args = [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])
    out = {}
    for path in ("off", "force"):
        mode(path)
        out[path] = _rule_and_grads(dr.gated_delta_rule, *args, w)
    assert out["off"][0].dtype == jnp.bfloat16
    assert rel(out["force"][0], out["off"][0]) < 1e-2
    for a, b in zip(out["force"][1], out["off"][1]):
        assert rel(a, b) < 1e-2


def test_a_bfloat16_recurrence_fails_the_rules_tolerance():
    *args, w = rule_inputs("mixed")
    want, _ = _rule_and_grads(recurrent, *args, w)
    rounded = [a.astype(jnp.bfloat16).astype(jnp.float32) for a in args]
    got, _ = _rule_and_grads(recurrent, *rounded, w)
    assert rel(got, want) > 10 * RULE_TOL


def test_the_dispatch_says_why(mode):
    """Off the chip ``auto`` takes the scan (``platform``); a head the
    lanes do not tile takes it even under ``force`` (``shape``)."""
    counter = tel.registry.get("linear_attn.dispatch")
    q, k, v, g, beta, _ = rule_inputs("mixed", Tq=64, d=8)
    for m, why in (("auto", "platform"), ("force", "shape")):
        mode(m)
        before = counter.value(decision="xla", why=why)
        # a new function each time: a trace is cached by the function
        jax.eval_shape(lambda *a: dr.gated_delta_rule(*a), q, k, v, g, beta)
        assert counter.value(decision="xla", why=why) == before + 1


# ------------------------------------------------------------------ stack
def tiny_cfg(key_heads=8, dtype="float32"):
    """The published file at toy widths: 8 key heads (two groups of the
    layer's 4) serving 16 value heads of 8, 4 query heads of 16 on 2 KV
    heads, 3 of 16 routed experts a token with 4 held; weights at 0.1,
    where float32 holds the stack's gradients to a millionth of a float64
    reference (at 0.3 the float32 reference itself is 6e-3 from its
    float64 self: random layers that deep amplify rounding)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "qwen3_next_80b_a3b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, linear_num_key_heads=key_heads,
               linear_num_value_heads=2 * key_heads, linear_key_head_dim=8,
               linear_value_head_dim=8, moe_intermediate_size=16,
               shared_expert_intermediate_size=16, num_experts=4,
               num_experts_per_tok=3, vocab_size=48, compute_dtype=dtype)
    cfg["input"] = dict(cfg["input"], vocab_size=48)
    cfg["deployment"] = dict(cfg["deployment"], num_experts_routed=16,
                             held=[0, 4])
    cfg["assumed"] = dict(cfg["assumed"], initializer_std=0.1)
    return cfg


def _both(cfg, seed=5, precision="float32"):
    w = ref.init_weights(seed, cfg)
    ids = np.random.default_rng(8).integers(0, 48, (B, T), dtype=np.int32)
    y = np.ones((B, 1), np.float32)
    net = program.build(cfg, w, {"seq_len": T})
    lf = net._build_loss_fn()

    def prog(p):
        return lf(p, net.state, None, (jnp.asarray(ids),), (jnp.asarray(y),),
                  (None,), (None,))[0]

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog)(net.params)
        lr, gr = jax.value_and_grad(
            lambda p: ref.loss(p, (ids, y), cfg, precision))(w)
    return (float(lp), {k: gp[k.split("/")[0]][k.split("/")[1]] for k in gr},
            float(lr), gr)


#: float32 on both sides: the loss agrees to 2e-7 (one order of summation
#: against another); every gradient leaf to 1.8e-5, the worst the decay's
#: leaves (A_log, dt_bias), whose gradient is a sum over positions of terms
#: that the chunked form orders differently; 1e-5 and 2e-4 leave room, and
#: the bfloat16 reference fails the loss's by 100x (test below)
LOSS_TOL, GRAD_TOL = 1e-5, 2e-4


def test_the_stack_is_the_reference():
    lp, gp, lr, gr = _both(tiny_cfg())
    assert abs(lp - lr) <= LOSS_TOL * abs(lr)
    for leaf in gr:
        assert rel(gp[leaf], gr[leaf]) < GRAD_TOL, leaf


def test_a_bfloat16_reference_fails():
    _, _, l32, _ = _both(tiny_cfg())
    _, _, l16, _ = _both(tiny_cfg(), precision="bfloat16")
    assert abs(l16 - l32) > 10 * LOSS_TOL * abs(l32)


def test_the_stack_trains_through_fit_on_device():
    """Two Adam steps through the cell's entry point, recomputing a decoder
    layer at a time: the losses are finite, every layer's mixer counts its
    chunks, and the linear layers keep their output."""
    cfg = tiny_cfg()
    net = program.build(cfg, ref.init_weights(1, cfg), {"seq_len": T})
    kept = tel.registry.get("attention.kept")
    before = kept.value(kind="linear", decision="kept")
    ids = np.random.default_rng(2).integers(0, 48, (4, T), dtype=np.int32)
    losses = net.fit_on_device(ids, np.ones((4, 1), np.float32), epochs=1,
                               batch_size=2)
    assert np.isfinite(losses).all()
    assert kept.value(kind="linear", decision="kept") == before + 3
    chunks = tel.registry.get("linear_attn.chunks")
    # two steps of 2 rows, 16 value heads, 80 positions in 2 chunks of 64
    for i in range(3):
        assert chunks.value(layer=f"l{i}.attn") == 2 * 2 * 16 * 2
    assert chunks.value(layer="l3.attn") == 0


def test_the_layer_in_groups_is_the_layer_whole(monkeypatch):
    """The grouping of key heads changes no number: one group of all 8 and
    four of 2 give the same output and gradient."""
    from deeplearning4j_tpu.nn.layers import decoder
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    lay = GatedDeltaNetLayer(n_key_heads=8, n_value_heads=16, key_size=8,
                             value_size=8)
    p, st, _ = lay.initialize(jax.random.PRNGKey(0), x.shape, jnp.float32)
    out = {}
    for per in (8, 2):
        monkeypatch.setattr(decoder, "_KEY_HEADS_A_GROUP", per)
        f = lambda p, x: jnp.sum(jnp.sin(lay.apply(p, x, st)[0]))
        with jax.default_matmul_precision("highest"):
            out[per] = jax.value_and_grad(f, argnums=(0, 1))(p, x)
    (l8, (p8, x8)), (l2, (p2, x2)) = out[8], out[2]
    assert abs(float(l8) - float(l2)) < 1e-5 * abs(float(l8))
    assert rel(x2, x8) < 1e-5
    for k in p8:
        assert rel(p2[k], p8[k]) < 1e-5, k


def test_the_layer_follows_the_published_channel_order():
    """``in_proj_qkvz`` is key head by key head ``[q, k, v, v, z, z]``: a
    weight that writes only key head 1's first value head's ``v`` columns
    moves only value head 2's output (heads 2j and 2j + 1 read key head
    j)."""
    lay = GatedDeltaNetLayer(n_key_heads=2, n_value_heads=4, key_size=8,
                             value_size=8)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64, 16))
    p, st, _ = lay.initialize(jax.random.PRNGKey(4), x.shape, jnp.float32)
    per_key = 2 * 8 + 2 * 2 * 8
    start = per_key + 2 * 8                   # key head 1: after its q, k
    bump = jnp.zeros_like(p["in_proj_qkvz"]).at[:, start:start + 8].set(0.5)
    def values(p):
        one = {name: w[0] for name, w in lay.groups(p).items()}
        return lay.mix(one, x, 2)[2]

    v = values(p)
    v2 = values(dict(p, in_proj_qkvz=p["in_proj_qkvz"] + bump))
    moved = np.abs(np.asarray(v2 - v)).max(axis=(0, 1, 3))
    assert moved[2] > 0 and moved[[0, 1, 3]].max() == 0


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of 16 of 64 experts (absent experts left out, as
    a share is computed): the routed parts add up to the layer that holds
    all 64, and the gated shared expert, which every chip computes alike,
    is counted once."""
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 32))

    def layer(held):
        return SparseExpertLayer(num_experts=64, top_k=10, n_hidden=8,
                                 shared_hidden=8, shared_gate=True, held=held,
                                 scoring="softmax")

    whole = layer(None)
    p, st, _ = whole.initialize(jax.random.PRNGKey(2), x.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = whole.apply(p, x, st)[0]
        shared = whole.apply(dict(p, W2=jnp.zeros_like(p["W2"])), x, st)[0]
        total = shared
        for first in range(0, 64, 16):
            part = dict(p, **{k: p[k][first:first + 16]
                              for k in ("W1", "W3", "W2")})
            lay = layer((first, 16))
            st_ = lay.initialize(jax.random.PRNGKey(2), x.shape,
                                 jnp.float32)[1]
            total = total + lay.apply(part, x, st_)[0] - shared
    assert rel(total, want) < 1e-5


def test_the_shared_gate_is_off_by_default():
    """Without ``shared_gate`` the layer has no gate weight and its program
    no gate's sigmoid; with it, a gate driven to 1 gives the ungated output
    bit for bit (the gate is the one thing it adds)."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32)).astype(
        jnp.bfloat16)
    off = SparseExpertLayer(num_experts=8, top_k=2, n_hidden=8,
                            shared_hidden=8)
    on = SparseExpertLayer(num_experts=8, top_k=2, n_hidden=8,
                           shared_hidden=8, shared_gate=True)
    p, st, _ = off.initialize(jax.random.PRNGKey(0), x.shape, jnp.bfloat16)
    assert "Wsg" not in p
    p_on, _, _ = on.initialize(jax.random.PRNGKey(0), x.shape, jnp.bfloat16)
    assert {k: v.shape for k, v in p_on.items() if k != "Wsg"} == \
        {k: v.shape for k, v in p.items()}

    def sigmoids(lay, p):
        return str(jax.make_jaxpr(lambda p, x: lay.apply(p, x, st)[0])(
            p, x)).count("logistic")

    # the router's and the experts' SiLU are in both; the gate is one more
    assert sigmoids(on, p_on) == sigmoids(off, p) + 1
    # a positive input through a gate of 1e4: sigmoid is exactly 1
    gate = jnp.full((32, 1), 1e4, jnp.bfloat16)
    xs = jnp.abs(x)
    a = off.apply(p, xs, st)[0]
    b = on.apply(dict(p, Wsg=gate), xs, st)[0]
    assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_the_full_layer_takes_the_masked_kernels_at_a_head_of_256():
    """16 query heads of 256 on 2 KV heads at 32,768 positions tile for the
    masked kernels, as every cell's layout does; the kernels' own guard is
    the one the dispatcher reads."""
    blocks = ca.causal_blocks(32768, 256, 256, None, 2)
    assert blocks is not None
    assert fa.fits_vmem_attention(blocks[0], blocks[1], 256, 2)


def test_the_builder_lays_out_one_period_and_refuses_what_it_does_not_build():
    cfg = tiny_cfg()
    net = qwen3_next(dict(cfg, num_experts=16), T, held=(0, 4))
    layers = {n: v.layer for n, v, _ in net.conf.vertices
              if n.endswith(".attn")}
    kinds = [type(layers[f"l{i}.attn"]).__name__ for i in range(4)]
    assert kinds == ["GatedDeltaNetLayer"] * 3 + ["CausalSelfAttentionLayer"]
    full = layers["l3.attn"]
    assert (full.gated, full.qk_norm, full.rotary_dim) == (True, True, 4)
    for key, value in (("use_sliding_window", True),
                       ("tie_word_embeddings", True),
                       ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
                       ("norm_topk_prob", False)):
        with pytest.raises(NotImplementedError):
            qwen3_next(dict(cfg, **{key: value}), T)
