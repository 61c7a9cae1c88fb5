"""The language-model heads form their gradients in the one pass that makes a
block's logits (``ops/lm_loss.py``): both heads against a plain reference
written here (whole logits, ``jax.nn.logsumexp``, ``jax.grad``) in float32
and bfloat16, with a cotangent other than 1 on the loss; one logits product a
block in the lowered gradient of a network whose head lies in a recomputed
segment, and the counter that says so; no weight-shaped product where nothing
is differentiated; the function's own contract."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import memory
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import decoder
from deeplearning4j_tpu.nn.layers.core import EmbeddingLayer
from deeplearning4j_tpu.nn.layers.decoder import (CausalLMOutputLayer,
                                                  ExitWeightedLMOutputLayer)
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.nn.vertices import GraphVertex, vertex
from deeplearning4j_tpu.ops import lm_loss
from deeplearning4j_tpu.runtime import telemetry as tel

B, T, D, V, R, BETA = 2, 8, 16, 50, 3, 0.1


def plain_causal(p, h, ids):
    """Whole logits at once, the mean over the positions with a next
    token."""
    logits = jnp.dot(h[:, :-1], p["W"], preferred_element_type=jnp.float32)
    picked = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def plain_exit_weighted(p, h, ids):
    """``h`` ``[R, B, T, d]``: whole logits of every pass, the exit
    distribution in probability space, ``sum_t p ce - beta H(p)``."""
    logits = jnp.dot(h, p["W"], preferred_element_type=jnp.float32)
    nxt = jnp.roll(ids, -1, axis=1)
    picked = jnp.take_along_axis(
        logits, jnp.broadcast_to(nxt, logits.shape[:-1])[..., None],
        axis=-1)[..., 0]
    ce = jax.nn.logsumexp(logits, axis=-1) - picked
    g = jax.nn.sigmoid(jnp.dot(h, p["Wg"],
                               preferred_element_type=jnp.float32)[..., 0]
                       + p["bg"].astype(jnp.float32))
    stay = jnp.cumprod(1.0 - g, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    prob = jnp.concatenate([(g * before)[:-1], before[-1:]], axis=0)
    loss = jnp.sum(prob * (ce + BETA * jnp.log(prob)), axis=0)
    return jnp.mean(loss[:, :-1])


HEADS = {
    "causal": (lambda: CausalLMOutputLayer(n_out=V), plain_causal,
               [(T, D), (T,)], (B, T, D)),
    "exit_weighted": (lambda: ExitWeightedLMOutputLayer(n_out=V, beta=BETA),
                      plain_exit_weighted, [(R, T, D), (T,)], (R, B, T, D)),
}


def head_world(kind, dtype):
    make, plain, shapes, h_shape = HEADS[kind]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    layer = make()
    params, state, _ = layer.initialize(keys[0], shapes, jnp.float32)
    if "bg" in params:   # a gate that is not at its symmetric start
        params["bg"] = jax.random.normal(keys[3], (1,)) * 0.5
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    h = jax.random.normal(keys[1], h_shape).astype(dtype)
    ids = jax.random.randint(keys[2], (B, T), 0, V, jnp.int32)

    def program(p, h):
        out, _, _ = layer.apply(p, [h, ids], state, train=True)
        return layer.loss_value(out, None)

    return program, (lambda p, h: plain(p, h, ids)), params, h


def close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape, (a.shape, b.shape)
    scale = max(np.abs(b).max(), 1e-12)
    assert np.abs(a - b).max() <= tol * scale, (np.abs(a - b).max(), scale)


# float32 to rounding; bfloat16 to a few of its 2**-8 steps: the two sides
# round the products back to the leaves' dtype after sums in another order
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


@pytest.mark.parametrize("kind,dtype,block", [
    ("causal", "float32", None), ("causal", "bfloat16", None),
    ("exit_weighted", "float32", 4), ("exit_weighted", "float32", T + 1),
    ("exit_weighted", "bfloat16", 4), ("exit_weighted", "bfloat16", T + 1)],
    ids=["causal-f32", "causal-bf16", "exit-f32-block_divides",
         "exit-f32-whole_row", "exit-bf16-block_divides",
         "exit-bf16-whole_row"])
def test_loss_and_every_gradient_leaf_equal_the_plain_head(monkeypatch, kind,
                                                           dtype, block):
    """With a cotangent of 3 on the loss: the backward rule scales what the
    forward rule kept."""
    if block is not None:
        monkeypatch.setattr(decoder, "LM_HEAD_BLOCK", block)
    program, plain, params, h = head_world(kind, jnp.dtype(dtype))
    with jax.default_matmul_precision("highest"):
        loss, pull = jax.vjp(program, params, h)
        gp, gh = pull(jnp.float32(3.0))
        want, want_pull = jax.vjp(plain, params, h)
        wp, wh = want_pull(jnp.float32(3.0))
    assert loss.shape == () and loss.dtype == jnp.float32
    assert abs(float(loss) - float(want)) <= 1e-6 * abs(float(want))
    assert set(gp) == set(wp) and gh.dtype == h.dtype
    close(gh, wh, TOL[dtype])
    for leaf in wp:
        assert gp[leaf].dtype == params[leaf].dtype
        close(gp[leaf], wp[leaf], TOL[dtype])


@vertex("test_lm_passes")
class _Passes(GraphVertex):
    """Test only: its input and twice its input stacked before the batch
    axis, as two passes of a repeated run."""

    def initialize(self, key, input_shapes, dtype):
        return {}, {}, (2,) + tuple(input_shapes[0])

    def apply(self, params, xs, state, *, train=False, rng=None, masks=None):
        return jnp.stack([xs[0], 2.0 * xs[0]]), state, None


def one_segment_net(kind, workspace_mode):
    """Embedding and head: one segment under ``every_4``."""
    g = (NeuralNetConfiguration.builder().seed(0)
         .updater(Adam(learning_rate=1e-3)).graph_builder()
         .add_inputs("tokens").set_input_types((T,))
         .add_layer("embed", EmbeddingLayer(n_in=V, n_out=D), "tokens"))
    if kind == "causal":
        g = g.add_layer("lm_head", CausalLMOutputLayer(n_out=V), "embed",
                        "tokens")
    else:
        g = (g.add_vertex("passes", _Passes(), "embed")
             .add_layer("lm_head", ExitWeightedLMOutputLayer(n_out=V),
                        "passes", "tokens"))
    net = ComputationGraph(g.set_outputs("lm_head").build()).init()
    net.set_workspace_mode(workspace_mode)
    return net


def _decisions(kind):
    series = tel.snapshot()["lm_head.gradients"]["series"]
    return {d: int(series.get(json.dumps({"decision": d, "layer": kind}), 0))
            for d in ("in_forward_kept", "in_forward")}


def _products(text, rows, cols):
    return len(re.findall(
        rf"dot_general.*-> tensor<{rows}x{cols}x", text))


@pytest.mark.parametrize("kind,rows", [("causal", T - 1),
                                       ("exit_weighted", 4)])
@pytest.mark.parametrize("mode,decision", [("every_4", "in_forward_kept"),
                                           ("none", "in_forward")])
def test_one_logits_product_a_block_in_the_gradient(monkeypatch, kind, rows,
                                                    mode, decision):
    """The lowered gradient of a network whose head lies inside a segment:
    the blocks' loop is there once, with one ``[block, vocab]`` product in
    its body and the two products that carry the loss back beside it, and
    the counter says where the gradients were formed."""
    monkeypatch.setattr(decoder, "LM_HEAD_BLOCK", 4)
    net = one_segment_net(kind, mode)
    ids = jnp.zeros((B, T), jnp.int32)
    y = jnp.ones((B, 1), jnp.float32)
    before = _decisions(kind)
    grad = jax.jit(jax.grad(lambda p: net._build_loss_fn()(
        p, net.state, None, (ids,), (y,), (None,), (None,))[0]))
    text = grad.lower(net.params).as_text()
    after = _decisions(kind)
    assert _products(text, rows, V) == 1
    assert _products(text, rows, D) == 1     # back to the hidden states
    assert _products(text, D, V) == 1        # the block's term of W's
    assert len(re.findall(r"stablehlo\.while", text)) == 1
    other = ({"in_forward_kept", "in_forward"} - {decision}).pop()
    assert after[decision] - before[decision] == 1
    assert after[other] == before[other]
    # and the gradients are those of the plain head on the same input
    grads = grad(net.params)
    h = net.params["embed"]["W"][ids]
    if kind == "causal":
        want = jax.grad(plain_causal)(net.params["lm_head"], h, ids)
    else:
        want = jax.grad(lambda p: plain_exit_weighted(
            p, jnp.stack([h, 2.0 * h]), ids))(net.params["lm_head"])
    for leaf in want:
        close(grads["lm_head"][leaf], want[leaf], 1e-4)


@pytest.mark.parametrize("kind", ["causal", "exit_weighted"])
def test_the_undifferentiated_head_makes_no_weight_shaped_product(monkeypatch,
                                                                  kind):
    """``score`` and a listener pay for logits and logsumexp alone, and
    count nothing."""
    monkeypatch.setattr(decoder, "LM_HEAD_BLOCK", 4)
    program, plain, params, h = head_world(kind, jnp.float32)
    before = _decisions(kind)
    text = jax.jit(program).lower(params, h).as_text()
    assert _decisions(kind) == before
    assert _products(text, D, V) == 0
    rows = T - 1 if kind == "causal" else 4
    assert _products(text, rows, V) == 1 and _products(text, rows, D) == 0
    with jax.default_matmul_precision("highest"):
        assert abs(float(program(params, h)) - float(plain(params, h))) \
            <= 1e-6 * abs(float(plain(params, h)))


def test_the_weights_cotangent_is_the_cross_entropy():
    """The function alone: the scalar's gradient with respect to the row
    weights is each position's cross-entropy, which is also handed out
    under ``stop_gradient``; kept and not kept are the same numbers."""
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    h = jax.random.normal(keys[0], (3, 4, D))
    W = jax.random.normal(keys[1], (D, V)) * 0.3
    nxt = jax.random.randint(keys[2], (3, 4), 0, V, jnp.int32)
    w = jax.random.uniform(keys[3], (3, 4))

    def f(h, W, w):
        return lm_loss.weighted_cross_entropy(h, W, nxt, w, layer="test")

    with jax.default_matmul_precision("highest"):
        (total, ce), pull = jax.vjp(f, h, W, w)
        gh, gW, gw = pull((jnp.float32(1.0), jnp.ones_like(ce)))
        logits = jnp.einsum("ncd,dv->ncv", h, W)
        want_ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, nxt[..., None], -1)[..., 0]
        kept = memory.checkpoint(lambda h, W, w: f(h, W, w)[0],
                                 memory.resolve_policy("full"))
        kh, kW, kw = jax.grad(kept, (0, 1, 2))(h, W, w)
    close(ce, want_ce, 1e-6)
    close(total, jnp.sum(w * want_ce), 1e-6)
    close(gw, want_ce, 1e-6)     # the cotangent on ``ce`` went nowhere
    for got, same in ((kh, gh), (kW, gW), (kw, gw)):
        assert (np.asarray(got) == np.asarray(same)).all()


def test_forward_mode_through_a_training_head_is_refused():
    program, _, params, h = head_world("causal", jnp.float32)
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda h: program(params, h), (h,), (jnp.ones_like(h),))
