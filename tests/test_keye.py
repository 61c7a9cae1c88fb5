"""The Keye-VL-2.0 language-model stack (grouped-query attention whose open
keys an indexer selects, a softmax router) against its plain reference, at
tiny widths on the CPU in float32 with seeded weights: every layer and the
whole stack (logits, loss, every gradient leaf with the indexer's three
exactly nought, three Adam steps through ``fit_on_device`` with and without
recomputation), the selection alone against ``jax.lax.top_k`` on rows shorter
than, as long as and longer than ``topk`` and on rows with ties across the
threshold, the selected attention through one block, blocked rows and the
masked kernels, the open-key counter against its closed form, the layer with
every key open against ``CausalSelfAttentionLayer``, softmax routing against
the reference, the share test (the 16 shares of 8 experts add up to the uncut
layer), what must NOT pass (one key more or fewer a row, sigmoid routing,
bfloat16), the builder's refusals, and the counters after one call."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import flops_dsa
from benchmarks.reference import keye_vl2_30b_a3b as ref
from benchmarks.reference import optim
from deeplearning4j_tpu.models.decoder_stack import VERTICES_PER_LAYER
from deeplearning4j_tpu.models.keye import keye_vl2
from deeplearning4j_tpu.nn.layers.decoder import (CausalSelfAttentionLayer,
                                                  SparseExpertLayer,
                                                  SparseSelectAttentionLayer)
from deeplearning4j_tpu.ops import causal_attention as ca
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.ops import sparse_attention as sa
from deeplearning4j_tpu.runtime import telemetry as tel

program = importlib.import_module(
    "benchmarks.configs.keye_vl2_30b_a3b.program")

T, B, LAYERS, HIDDEN, TOPK = 16, 2, 3, 32, 5
ADAM = {"kind": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INDEXER = ("WqI", "WkI", "Ww")


def tiny_cfg(held=(0, 4), routed=16, topk=TOPK, dtype="float32"):
    """The published file at toy widths: 4 query heads on 2 KV heads of 8, 3
    index heads of 4 against one index key, the ``topk`` best keys a query, 2
    of ``routed`` experts a token."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "keye_vl2_30b_a3b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=HIDDEN, num_attention_heads=4,
               num_key_value_heads=2, head_dim=8, moe_intermediate_size=16,
               num_experts_per_tok=2, num_experts=held[1],
               num_local_experts=held[1], vocab_size=48,
               num_hidden_layers=LAYERS, compute_dtype=dtype)
    cfg["sa_config"] = dict(cfg["sa_config"], indexer_num_heads=3,
                            indexer_head_dim=4, topk=topk)
    cfg["deployment"] = dict(cfg["deployment"], num_experts_routed=routed,
                             held=list(held))
    cfg["assumed"] = dict(cfg["assumed"], initializer_std=0.3, updater=ADAM)
    return cfg


def build(cfg, weights, workspace_mode=None):
    """The benchmark's own ``program.build`` (it recomputes a decoder layer
    at a time); ``workspace_mode`` overrides that."""
    net = program.build(cfg, weights, {"seq_len": T})
    if workspace_mode is not None:
        net.set_workspace_mode(workspace_mode)
    return net


def _loss_and_grads(net, ids):
    loss_fn = net._build_loss_fn()
    y = np.ones((ids.shape[0], 1), np.float32)
    (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        net.params, net.state, None, (jnp.asarray(ids),), (jnp.asarray(y),),
        (None,), (None,))
    return loss, program._flat(grads)


@pytest.fixture(scope="module")
def world():
    cfg = tiny_cfg()
    weights = ref.init_weights(7, cfg)
    ids = np.random.default_rng(3).integers(0, cfg["vocab_size"], (B, T),
                                            dtype=np.int32)
    net = build(cfg, weights)
    acts = net.feed_forward(ids)
    with jax.default_matmul_precision("highest"):
        loss, grads = _loss_and_grads(net, ids)
        ref_loss, ref_grads = jax.value_and_grad(ref.loss)(
            weights, (ids, np.ones((B, 1), np.float32)), cfg, "float32")
    return dict(cfg=cfg, weights=weights, ids=ids, net=net, acts=acts,
                loss=loss, grads=grads, ref_loss=ref_loss,
                ref_grads=ref_grads)


def gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-12)


def close(a, b, tol=2e-4):
    assert gap(a, b) <= tol, gap(a, b)


def _ref_layer(w, i, h_in, cfg):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref._layer(w, i, h_in[b], cfg, "float32")
                          for b in range(h_in.shape[0])])


def _h_in(world, i):
    return world["acts"]["embed" if i == 0 else f"l{i - 1}.mlp_res"]


@pytest.mark.parametrize("i", range(LAYERS))
def test_each_layer_matches_the_reference(world, i):
    """Layer ``i`` alone (every layer is of the one kind: selected
    attention, then sparse experts): the reference's layer on the program's
    own input to it gives the program's output."""
    want = _ref_layer(world["weights"], i, _h_in(world, i), world["cfg"])
    close(world["acts"][f"l{i}.mlp_res"], want)


def test_stack_logits_match_the_reference(world):
    w, cfg, ids = world["weights"], world["cfg"], world["ids"]
    with jax.default_matmul_precision("highest"):
        want = jax.nn.softmax(ref.logits(w, jnp.asarray(ids), cfg), -1)
    close(world["acts"]["lm_head"], want)
    close(world["net"].output(ids), want)


def test_stack_loss_matches_the_reference(world):
    assert abs(float(world["loss"]) - float(world["ref_loss"])) \
        <= 1e-5 * float(world["ref_loss"])


LEAVES = [n for n, _, _ in ref.layer_table(tiny_cfg())]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference(world, leaf):
    """The indexer's three matrices enter the loss through the indices of a
    top-k alone: their gradient is exactly nought on both sides."""
    if leaf.split("/")[1] in INDEXER:
        assert not np.asarray(world["ref_grads"][leaf]).any()
        assert not np.asarray(world["grads"][leaf]).any()
        return
    close(world["grads"][leaf], world["ref_grads"][leaf], tol=5e-4)


@pytest.mark.parametrize("workspace", ["none", f"every_{VERTICES_PER_LAYER}"])
def test_three_adam_steps_through_fit_on_device(world, workspace):
    cfg, w = world["cfg"], world["weights"]
    rows = np.random.default_rng(5).integers(0, cfg["vocab_size"],
                                             (3 * B, T), dtype=np.int32)
    y = np.ones((3 * B, 1), np.float32)
    net = build(cfg, jax.tree.map(jnp.copy, w), workspace_mode=workspace)
    with jax.default_matmul_precision("highest"):
        losses = net.fit_on_device(rows, y, epochs=1, batch_size=B)
        p, state = w, optim.init_state(ADAM, w)
        want = []
        for s in range(3):
            batch = (rows[s * B:(s + 1) * B], y[:B])
            l, g = jax.value_and_grad(ref.loss)(p, batch, cfg, "float32")
            p, state = optim.apply(ADAM, g, state, p, s)
            want.append(float(l))
    np.testing.assert_allclose(losses, want, rtol=2e-5)
    got = program.params(net)
    assert set(got) == set(p)
    frozen = [k for k in p if k.split("/")[1] in INDEXER]
    assert len(frozen) == 3 * LAYERS
    for k in frozen:
        # nought in, nought out: Adam leaves a zero gradient's leaf alone
        assert np.array_equal(np.asarray(got[k]), np.asarray(w[k]))
        assert np.array_equal(np.asarray(p[k]), np.asarray(w[k]))
        assert not np.asarray(program.first_moment(net)[k]).any()
    moved = [np.linalg.norm(np.asarray(got[k] - p[k]))
             / max(np.linalg.norm(np.asarray(p[k] - w[k])), 1e-12)
             for k in p if k not in frozen]
    assert max(moved) < 2e-2, max(moved)
    close(program.first_moment(net)["l1.mlp/W1"], state["m"]["l1.mlp/W1"],
          tol=1e-3)


# ---------------------------------------------------------------- selection
def _top_k_mask(scores, topk):
    """Row by row with ``jax.lax.top_k`` on the causal keys."""
    scores = np.asarray(scores)
    out = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for t in range(scores.shape[1]):
            if t + 1 <= topk:
                out[b, t, :t + 1] = True
            else:
                _, idx = jax.lax.top_k(jnp.asarray(scores[b, t, :t + 1]),
                                       topk)
                out[b, t, np.asarray(idx)] = True
    return out


def _index_inputs(seed, t, rounded=False):
    k0 = jax.random.PRNGKey(seed)
    q = jax.random.normal(k0, (B, t, 3, 4))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (B, t, 4))
    w = jax.random.normal(jax.random.fold_in(k0, 2), (B, t, 3))
    if rounded:
        # whole numbers: many keys of a row score the same
        q, k, w = jnp.round(q), jnp.round(k), jnp.round(w)
    return q, k, w


@pytest.mark.parametrize("t,topk,block,span", [
    (8, 12, 256, 2048), (12, 12, 256, 2048), (32, 12, 256, 2048),
    (32, 12, 8, 8), (64, 16, 8, 16), (64, 24, 16, 32), (64, 100, 8, 16)],
    ids=["shorter", "equal", "longer", "longer.blocked",
         "blocked.reach16", "blocked.reach32", "blocked.all_open"])
@pytest.mark.parametrize("rounded", [False, True], ids=["plain", "ties"])
def test_the_selection_is_lax_top_k(t, topk, block, span, rounded):
    """Exactly ``min(t + 1, topk)`` keys open a row, the ones ``lax.top_k``
    takes (the lower index first among equals), whatever the blocking; the
    counts are the mask's."""
    q, k, w = _index_inputs(t + topk, t, rounded)
    scores = sa.index_scores(q, k, w)
    mask, keys, ties = sa.open_keys(q, k, w, topk, block=block, span=span)
    want = _top_k_mask(scores, topk)
    assert np.array_equal(np.asarray(mask), want)
    assert np.asarray(mask).sum(-1).tolist() == \
        [[min(i + 1, topk) for i in range(t)]] * B
    assert int(keys) == B * flops_dsa.open_pairs(t, topk)
    # rows whose topk-th and next scores are equal, counted from the scores
    srt = -np.sort(-np.where(np.tril(np.ones((t, t), bool)),
                             np.asarray(scores), -np.inf), axis=-1)
    tied = sum(int(srt[b, i, topk - 1] == srt[b, i, topk])
               for b in range(B) for i in range(topk, t))
    assert int(ties) == tied
    # (unrounded rows tie too, at nought: three ReLUs all shut)
    assert tied > 0 or not (rounded and t > topk)


def test_a_row_built_with_ties_across_the_threshold():
    """Scores 3, 1, 1, 1, 1, 2, 1 for the last query and ``topk`` 4: the two
    above the tie, then the two LOWEST indices of the five tied keys."""
    row = jnp.asarray([3.0, 1, 1, 1, 1, 2, 1])
    scores = jnp.tile(row, (1, 7, 1))
    mask, tied = sa.select(scores, 4)
    assert np.asarray(mask)[0, 6].tolist() == \
        [True, True, True, False, False, True, False]
    assert np.asarray(mask)[0, 4].tolist() == \
        [True, True, True, True, False, False, False]
    assert np.asarray(tied)[0].tolist() == [False] * 4 + [True] * 3
    assert np.array_equal(np.asarray(mask), _top_k_mask(scores, 4))
    # the reference's own mask agrees, tie for tie
    assert np.array_equal(np.asarray(ref.open_keys(scores[0], 0, 4)),
                          np.asarray(mask)[0])


def test_the_index_scores_are_the_references():
    q, k, w = _index_inputs(1, 24)
    with jax.default_matmul_precision("highest"):
        got = sa.index_scores(q, k, w)
        want = jnp.stack([ref.index_scores(q[b], k[b], w[b], "float32")
                          for b in range(B)])
    close(got, want, tol=1e-5)
    assert got.dtype == jnp.float32
    # bfloat16 operands still accumulate and sum in float32
    low = sa.index_scores(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), w)
    assert low.dtype == jnp.float32


# ---------------------------------------------------------------- attention
@pytest.mark.parametrize("t,block,mode,labels", [
    (32, 32, "auto", dict(decision="one_block")),
    (32, 8, "auto", dict(decision="blocked_rows", why="platform")),
    (256, 32, "force", dict(decision="kernel"))],
    ids=["one_block", "blocked_rows", "kernel"])
def test_selected_attention_through_one_block_and_blocked_rows(t, block, mode,
                                                              labels):
    """4 query heads on 2 KV heads under a mask that is data: one block,
    blocked rows (off the chip, ``why=platform``), the masked kernels
    (``force``: the interpreter) and a direct softmax over the open keys
    agree, gradients too; the site is counted under ``kind=sparse``."""
    k0 = jax.random.PRNGKey(4)
    q = jax.random.normal(k0, (B, t, 4, 8))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (B, t, 2, 8))
    v = jax.random.normal(jax.random.fold_in(k0, 2), (B, t, 2, 8))
    mask, _, _ = sa.open_keys(*_index_inputs(9, t), 6)
    run = lambda q, k, v: ca.causal_attention(q, k, v, block=block,
                                              select=mask)
    counter = tel.registry.get("attention.dispatch")
    old = fa.set_mode(mode)
    try:
        before = counter.value(kind="sparse", **labels)
        got = run(q, k, v)
        assert counter.value(kind="sparse", **labels) == before + 1

        def direct(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2)) \
                / np.sqrt(8.0)
            p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), -1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, 2, axis=2))

        close(got, direct(q, k, v), tol=1e-5)
        for arg in range(3):
            g = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) ** 2),
                                   argnums=arg)(q, k, v)
            close(g(run), g(direct), tol=1e-5)
        with pytest.raises(ValueError, match="window"):
            ca.causal_attention(q, k, v, window=8, select=mask)
    finally:
        fa.set_mode(old)


def _select_layer(topk, **kw):
    return SparseSelectAttentionLayer(
        n_heads=4, n_kv_heads=2, head_size=8, qk_norm=True, rope_theta=1e7,
        index_heads=3, index_head_size=4, topk=topk, **kw)


def _attn_params(world, i=0):
    return {k.split("/")[1]: v for k, v in world["weights"].items()
            if k.startswith(f"l{i}.attn/")}


def test_with_every_key_open_the_layer_is_causal_self_attention(world):
    """``topk >= T``: nothing is closed but by causality, and the layer is
    ``CausalSelfAttentionLayer`` with the same per-head norms."""
    params = _attn_params(world)
    x = world["acts"]["l0.attn_norm"]
    plain = CausalSelfAttentionLayer(n_heads=4, n_kv_heads=2, head_size=8,
                                     qk_norm=True, rope_theta=1e7)
    want, _, _ = plain.apply({k: v for k, v in params.items()
                              if k not in INDEXER}, x, {})
    for topk in (T, T + 7):
        got, _, _ = _select_layer(topk).apply(params, x, {})
        close(got, want, tol=1e-6)
    selected, _, _ = _select_layer(TOPK).apply(params, x, {})
    assert gap(selected, want) > 1e-2
    # without the norms it is another layer
    bare = CausalSelfAttentionLayer(n_heads=4, n_kv_heads=2, head_size=8,
                                    rope_theta=1e7)
    other, _, _ = bare.apply({k: v for k, v in params.items()
                              if k[0] == "W" and k not in INDEXER}, x, {})
    assert gap(other, want) > 1e-2


def _products(jaxpr):
    """``dot_general``s in a jaxpr, through every nested one."""
    return sum((eqn.primitive.name == "dot_general")
               + sum(_products(sub)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def test_the_mask_is_kept_with_the_output(world, monkeypatch):
    """Inside a recomputed segment the layer keeps its heads' output (it is
    twice the hidden size wide) and, with it, the mask the blocks' backward
    reads: the one product the kept output spares a one-block layer
    (``tests/test_memory_remat.py`` counts it for the other kinds) AND the
    indexer's four (three projections, the index products) are gone from
    every layer's recomputation, and the gradients are equal to the last
    bit."""
    from deeplearning4j_tpu.nn.layers import decoder as decmod
    net, ids = world["net"], jnp.asarray(world["ids"])
    loss_fn = net._build_loss_fn()
    y = jnp.ones((B, 1), jnp.float32)

    def grad():
        return jax.jit(jax.grad(lambda p: loss_fn(
            p, net.state, None, (ids,), (y,), (None,), (None,))[0]))

    kept_n = _products(jax.make_jaxpr(grad())(net.params).jaxpr)
    kept = grad()(net.params)
    monkeypatch.setattr(decmod, "_keeps_output", lambda *a: False)
    again_n = _products(jax.make_jaxpr(grad())(net.params).jaxpr)
    again = grad()(net.params)
    assert again_n - kept_n == LAYERS * (1 + 4), (kept_n, again_n)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("topk", [TOPK - 1, TOPK + 1],
                         ids=["one_fewer", "one_more"])
def test_one_key_more_or_fewer_a_row_is_not_the_reference(world, topk):
    """What the layer tests would not let pass: a selection that opens one
    key too many or too few."""
    params = _attn_params(world)
    x = world["acts"]["l0.attn_norm"]
    right, _, _ = _select_layer(TOPK).apply(params, x, {})
    wrong, _, _ = _select_layer(topk).apply(params, x, {})
    assert gap(wrong, right) > 50 * 2e-4


def test_bfloat16_is_not_the_reference(world):
    """The tolerance of the layer tests sits below the next precision down:
    the same stack computed in bfloat16 fails it."""
    cfg = tiny_cfg(dtype="bfloat16")
    net = build(cfg, world["weights"])
    acts = net.feed_forward(world["ids"])
    want = _ref_layer(world["weights"], 0, world["acts"]["embed"],
                      world["cfg"])
    assert gap(acts["l0.mlp_res"].astype(jnp.float32), want) > 10 * 2e-4


# ------------------------------------------------------------------ experts
def test_softmax_routing_is_the_references():
    """Softmax over ALL experts in float32, the largest, renormalised over
    the chosen; sigmoid scores choose the same experts and weigh them
    otherwise."""
    x = jax.random.normal(jax.random.PRNGKey(2), (B * T, HIDDEN))
    wr = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (HIDDEN, 16))
    e, w = moe.route(x, wr, 2, 1.0, scoring="softmax")
    with jax.default_matmul_precision("highest"):
        ref_e, ref_w = ref._route({"Wr": wr}, "", x, tiny_cfg(), "float32")
    assert np.array_equal(np.asarray(e), np.asarray(ref_e))
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref_w), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    p = np.asarray(jax.nn.softmax(x @ wr, -1))
    np.testing.assert_allclose(
        np.asarray(w)[:, 0],
        np.take_along_axis(p, np.asarray(e), 1)[:, 0]
        / np.take_along_axis(p, np.asarray(e), 1).sum(-1), rtol=1e-5)
    se, sw = moe.route(x, wr, 2, 1.0)
    assert np.array_equal(np.asarray(se), np.asarray(e))   # monotone scores
    assert np.abs(np.asarray(sw) - np.asarray(w)).max() > 0.05
    with pytest.raises(ValueError, match="scoring"):
        moe.route(x, wr, 2, 1.0, scoring="tanh")


def _expert_layer(cfg, held, scoring="softmax"):
    return SparseExpertLayer(
        num_experts=cfg["deployment"]["num_experts_routed"],
        top_k=cfg["num_experts_per_tok"],
        n_hidden=cfg["moe_intermediate_size"], held=held, scoring=scoring)


def _expert_params(w, pre, held=None):
    p = {k: w[pre + k] for k in ("Wr", "W1", "W3", "W2")}
    if held is not None:
        first, count = held
        for k in ("W1", "W3", "W2"):
            p[k] = p[k][first:first + count]
    return p


def test_sigmoid_routing_is_not_the_reference(world):
    cfg, w = world["cfg"], world["weights"]
    b = world["acts"]["l0.mlp_norm"]
    with jax.default_matmul_precision("highest"):
        want = ref._experts(w, "l0.mlp/", b.reshape(-1, HIDDEN), cfg,
                            "float32").reshape(b.shape)
    soft, _, _ = _expert_layer(cfg, (0, 4)).apply(
        _expert_params(w, "l0.mlp/"), b, {})
    sig, _, _ = _expert_layer(cfg, (0, 4), "sigmoid").apply(
        _expert_params(w, "l0.mlp/"), b, {})
    close(soft, want)
    assert gap(sig, want) > 50 * 2e-4


def test_the_shares_add_up_to_the_uncut_layer():
    """The deployment's 16 shares of 8 experts each, ``held=(8j, 8)``: their
    parts (there is no shared expert to count once) are the whole layer as
    the reference computes it with all 128 experts held, 8 a token."""
    cfg = dict(tiny_cfg(held=(0, 128), routed=128), num_experts_per_tok=8)
    w = ref.init_weights(11, cfg)
    pre = "l1.mlp/"
    b = jax.random.normal(jax.random.PRNGKey(0), (B * T, HIDDEN), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref._experts(w, pre, b, cfg, "float32")
        total, tokens = 0.0, 0
        for first in range(0, 128, 8):
            layer = _expert_layer(cfg, (first, 8))
            _, state, _ = layer.initialize(jax.random.PRNGKey(0),
                                           (T, HIDDEN), jnp.float32)
            y, state, _ = layer.apply(_expert_params(w, pre, (first, 8)), b,
                                      state, train=True)
            total = total + y
            tokens += int(state["here"])
        uncut, _, _ = _expert_layer(cfg, None).apply(
            _expert_params(w, pre), b, {})
    assert tokens == B * T * 8             # every choice is some share's
    close(total, whole)
    close(uncut, whole)


# ------------------------------------------------------ builder and counters
@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("mlp_only_layers", [0]),
    ("decoder_sparse_step", 2), ("attention_bias", True),
    ("tie_word_embeddings", True),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0})])
def test_the_builder_refuses_what_it_does_not_build(key, value):
    with pytest.raises(NotImplementedError, match=key):
        keye_vl2(dict(tiny_cfg(), **{key: value}), T)


def test_the_builder_refuses_image_inputs_and_an_empty_selection():
    with pytest.raises(NotImplementedError, match="image_inputs"):
        keye_vl2(tiny_cfg(), T, image_inputs=True)
    with pytest.raises(NotImplementedError, match="topk"):
        keye_vl2(tiny_cfg(topk=0), T)
    cfg = tiny_cfg()
    cfg["sa_config"]["indexer_num_kv_heads"] = 2
    with pytest.raises(NotImplementedError, match="indexer_num_kv_heads"):
        keye_vl2(cfg, T)


def test_counters_after_one_call(world):
    cfg = world["cfg"]
    net = build(cfg, jax.tree.map(jnp.copy, world["weights"]))
    rows = np.random.default_rng(9).integers(0, cfg["vocab_size"],
                                             (2 * B, T), dtype=np.int32)

    def total(name, **labels):
        m = tel.registry.get(name)
        if m is None:
            return 0
        return sum(v for k, v in m.series().items()
                   if all((lk, lv) in k for lk, lv in labels.items()))

    names = ("moe.tokens", "moe.assignments", "moe.dropped",
             "sparse_attn.keys", "sparse_attn.ties")
    before = {n: total(n) for n in names}
    sparse0 = total("attention.dispatch", kind="sparse")
    soft0 = total("moe.route", select="plain", scoring="softmax")
    other0 = total("moe.route") - soft0
    net.fit_on_device(rows, np.ones((2 * B, 1), np.float32), epochs=1,
                      batch_size=B)
    grew = {n: total(n) - before[n] for n in names}
    steps = 2
    # open keys summed over the queries of a step: the closed form, a layer
    assert grew["sparse_attn.keys"] == \
        LAYERS * steps * B * flops_dsa.open_pairs(T, TOPK)
    assert flops_dsa.open_pairs(T, TOPK) == sum(min(t + 1, TOPK)
                                                for t in range(T))
    per_layer = {dict(k)["layer"]: v for k, v in
                 tel.registry.get("sparse_attn.keys").series().items()}
    assert {f"l{i}.attn" for i in range(LAYERS)} <= set(per_layer)
    assert grew["sparse_attn.ties"] >= 0
    assert grew["moe.assignments"] == LAYERS * steps * B * T * 2
    assert 0 < grew["moe.tokens"] < grew["moe.assignments"]
    assert grew["moe.dropped"] == 0
    # once a traced site: every layer's attention, every layer's router
    assert total("attention.dispatch", kind="sparse") - sparse0 >= LAYERS
    assert total("moe.route", select="plain", scoring="softmax") - soft0 \
        >= LAYERS
    assert total("moe.route") - total("moe.route", select="plain",
                                      scoring="softmax") == other0


def test_the_cells_sizes_read_1792_keys_a_query():
    """At the cell's sizes the counter must read 2 x 14,681,088 a layer a
    step: 1,792.125 keys a query."""
    assert flops_dsa.open_pairs(8192, 2048) == 14_681_088
    assert flops_dsa.open_pairs(8192, 2048) / 8192 == 1792.125
    assert flops_dsa.open_pairs(2048, 2048) == flops_dsa.causal_pairs(2048)


def test_reference_layer_in_chunks_equals_one_chunk(monkeypatch):
    """The reference's own chunking (keys, values and index keys whole, then
    8 positions at a time) changes nothing."""
    cfg = tiny_cfg()
    w = ref.init_weights(17, cfg)
    h = jax.random.normal(jax.random.PRNGKey(6), (32, HIDDEN))

    def run(chunk):
        monkeypatch.setattr(ref, "_Q_BLOCK", chunk)
        with jax.default_matmul_precision("highest"):
            out = ref._layer(w, 0, h, cfg, "float32")
            g = jax.grad(lambda w_, h_: jnp.sum(
                ref._layer(w_, 0, h_, cfg, "float32") ** 2),
                argnums=(0, 1))(w, h)
        return out, g

    (one, g1), (many, g2) = run(32), run(8)
    close(many, one, tol=1e-5)
    close(g2[1], g1[1], tol=1e-5)
    for leaf in g1[0]:
        if leaf.startswith("l0."):
            close(g2[0][leaf], g1[0][leaf], tol=1e-5)
