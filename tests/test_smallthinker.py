"""The SmallThinker stack (positionless full layers among rotated window
layers, a softmax router that reads the layer's input before attention,
ReGLU experts) against its plain reference, at tiny widths on the CPU in
float32 with seeded weights: every layer (a NoPE full layer and window
layers whose window is shorter than the row) and the whole stack (logits,
loss, every gradient leaf, three Adam steps through ``fit_on_device`` with
and without recomputation); the share test (the 8 shares of 8 of 64 experts
add up to the uncut layer); the stand-in for the experts held elsewhere
(their router columns get no gradient); the early router (perturb the
experts' input alone and the choice stays); ``held_experts`` against plain
``jnp`` and the scatter-add it replaced, forward and backward, through the
segment-sum kernel and its ``jnp`` form, with rows at relu's zero; no
rotary tables in a NoPE layer; the layouts read layer by layer; what must
NOT pass (bfloat16, the router on its own input, SwiGLU); the builder's
refusals; the new counter labels; and the lowered text of every other
builder, which the new options' defaults leave as it was."""

import hashlib
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import optim
from benchmarks.reference import smallthinker_21b_a3b as ref
from deeplearning4j_tpu.models.decoder_stack import VERTICES_PER_LAYER
from deeplearning4j_tpu.models.smallthinker import smallthinker
from deeplearning4j_tpu.nn.layers.decoder import (CausalSelfAttentionLayer,
                                                  SparseExpertLayer)
from deeplearning4j_tpu.ops import causal_attention as ca
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.runtime import telemetry as tel

program = importlib.import_module(
    "benchmarks.configs.smallthinker_21b_a3b.program")

T, B, LAYERS, HIDDEN, WINDOW = 16, 2, 4, 32, 4
ADAM = {"kind": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


def tiny_cfg(held=(0, 4), routed=8, dtype="float32"):
    """The published file at toy widths: 14 query heads on 2 KV heads of 8
    (7 a KV head, as published), a window of 4 on a row of 16, 2 of
    ``routed`` experts a token; the layouts as the file has them (layer 0
    full and positionless, layers 1-3 rotated under the window). Half the
    experts are held, so that every layer has tokens that chose two held
    experts: under the file's stand-in only those give the router a
    gradient (with 4 of 16 held some layers had none, and their router's
    gradient was rounding alone)."""
    cfg = _config("smallthinker_21b_a3b")
    cfg.update(hidden_size=HIDDEN, num_attention_heads=14,
               num_key_value_heads=2, head_dim=8, moe_ffn_hidden_size=16,
               moe_num_active_primary_experts=2,
               moe_num_primary_experts=held[1], vocab_size=48,
               sliding_window_size=WINDOW, compute_dtype=dtype)
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
    """2e-4 of the largest magnitude: float32 against float32 in another
    order of summation (the program's grouped products, chunks and blocked
    softmax against the reference's dense forms) reads 1e-6 to 1e-5 here,
    and the same stack in bfloat16 reads above 2e-3."""
    assert gap(a, b) <= tol, gap(a, b)


def _ref_layer(w, i, h_in, cfg):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref._layer(w, i, h_in[b], cfg, "float32")
                          for b in range(h_in.shape[0])])


def _h_in(world, i):
    return world["acts"]["embed" if i == 0 else f"l{i - 1}.mlp_res"]


LAYER_IDS = ["nope_full", "rope_window.1", "rope_window.2", "rope_window.3"]


@pytest.mark.parametrize("i", range(LAYERS), ids=LAYER_IDS)
def test_each_layer_matches_the_reference(world, i):
    """Layer ``i`` alone: the reference's layer on the program's own input
    to it gives the program's output. Layer 0 is full and rotates nothing;
    the others rotate the whole head under a window of 4 on a row of 16."""
    want = _ref_layer(world["weights"], i, _h_in(world, i), world["cfg"])
    close(world["acts"][f"l{i}.mlp_res"], want)


def test_stack_logits_match_the_reference(world):
    w, cfg, ids = world["weights"], world["cfg"], world["ids"]
    with jax.default_matmul_precision("highest"):
        want = jax.nn.softmax(ref.logits(w, jnp.asarray(ids), cfg), -1)
    close(world["acts"]["lm_head"], want)
    close(world["net"].output(ids), want)


def test_stack_loss_matches_the_reference(world):
    """1e-5 of the loss: a mean of 30 float32 cross-entropies near ln 48."""
    assert abs(float(world["loss"]) - float(world["ref_loss"])) \
        <= 1e-5 * float(world["ref_loss"])


LEAVES = [n for n, _, _ in ref.layer_table(tiny_cfg())]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference(world, leaf):
    """5e-4: a gradient sums the forward's rounding over the row once more
    (the recomputed segment, the experts' custom backward); it reads below
    1e-5 here."""
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
    # the router's columns for experts held elsewhere get a gradient of
    # rounding alone under the stand-in, which Adam moves by its sign on
    # either side (``compare.py`` leaves such leaves out for that reason)
    first, count = cfg["deployment"]["held"]
    cut = lambda k, a: np.asarray(a)[:, first:first + count] \
        if k.endswith("mlp/Wr") else np.asarray(a)
    moved = [np.linalg.norm(cut(k, got[k] - p[k]))
             / max(np.linalg.norm(cut(k, p[k] - w[k])), 1e-12) for k in p]
    assert max(moved) < 2e-2, max(moved)
    close(program.first_moment(net)["l2.mlp/W1"], state["m"]["l2.mlp/W1"],
          tol=1e-3)


def test_bfloat16_is_not_the_reference(world):
    """The tolerance of the layer tests sits below the next precision down:
    the same stack computed in bfloat16 fails it."""
    net = build(tiny_cfg(dtype="bfloat16"), world["weights"])
    acts = net.feed_forward(world["ids"])
    want = _ref_layer(world["weights"], 0, world["acts"]["embed"],
                      world["cfg"])
    assert gap(acts["l0.mlp_res"].astype(jnp.float32), want) > 10 * 2e-4


# ---------------------------------------------------------------- attention
def _attn_params(world, i):
    return {k: world["weights"][f"l{i}.attn/{k}"]
            for k in ("Wq", "Wk", "Wv", "Wo")}


def test_a_nope_layer_makes_no_rotary_tables(world, monkeypatch):
    """``rope_type="none"``: no frequencies, no tables, queries and keys as
    projected; the same layer rotated differs."""
    def refuse(*a, **k):
        raise AssertionError("a rotary table was made")

    nope = CausalSelfAttentionLayer(n_heads=14, n_kv_heads=2, head_size=8,
                                    rope_type="none")
    rotated = CausalSelfAttentionLayer(n_heads=14, n_kv_heads=2, head_size=8,
                                       rope_theta=1.5e6)
    assert nope.inv_freq() is None
    a = world["acts"]["l0.attn_norm"]
    p = _attn_params(world, 0)
    monkeypatch.setattr(ca, "rotary_tables", refuse)
    q, k, _ = nope.project(p, a)
    np.testing.assert_array_equal(
        q, jnp.dot(a, p["Wq"]).reshape(q.shape))
    np.testing.assert_array_equal(
        k, jnp.dot(a, p["Wk"]).reshape(k.shape))
    with pytest.raises(AssertionError, match="rotary"):
        rotated.project(p, a)
    monkeypatch.undo()
    out_nope, _, _ = nope.apply(p, a, {})
    out_rot, _, _ = rotated.apply(p, a, {})
    assert gap(out_rot, out_nope) > 1e-2


def test_the_layouts_are_read_layer_by_layer():
    """The builder reads ``sliding_window_layout`` and ``rope_layout`` at
    each layer and assumes no period: a pattern that is not ``i % 4`` is
    laid out as written."""
    cfg = dict(tiny_cfg(), sliding_window_layout=[1, 0, 1, 0],
               rope_layout=[0, 0, 1, 1])
    net = smallthinker(cfg, T)
    attn = {n: v.layer for n, v, _ in net.conf.vertices
            if n.endswith(".attn")}
    assert [attn[f"l{i}.attn"].window for i in range(LAYERS)] == \
        [WINDOW, None, WINDOW, None]
    assert [attn[f"l{i}.attn"].rope_type for i in range(LAYERS)] == \
        ["none", "none", "default", "default"]
    assert {attn[f"l{i}.attn"].rope_theta for i in range(LAYERS)} == {1.5e6}


def test_the_stack_wires_the_router_to_the_attentions_input(world):
    got = {n: (v.layer.kind, list(ins)) for n, v, ins in
           world["net"].conf.vertices if n.endswith(".mlp")}
    assert got == {f"l{i}.mlp": ("sparse_experts",
                                 [f"l{i}.mlp_norm", f"l{i}.attn_norm"])
                   for i in range(LAYERS)}
    assert VERTICES_PER_LAYER == 6


# ------------------------------------------------------------------ experts
def _expert_layer(cfg, held, **kw):
    kw = dict(dict(activation="relu", early_router=True,
                   absent=cfg["deployment"]["absent"]), **kw)
    return SparseExpertLayer(
        num_experts=cfg["deployment"]["num_experts_routed"],
        top_k=cfg["moe_num_active_primary_experts"],
        n_hidden=cfg["moe_ffn_hidden_size"], held=held, scoring="softmax",
        **kw)


def _expert_params(w, pre, held=None):
    p = {k: w[pre + k] for k in ("Wr", "W1", "W3", "W2")}
    if held is not None:
        first, count = held
        for k in ("W1", "W3", "W2"):
            p[k] = p[k][first:first + count]
    return p


def _m_and_a(seed=0):
    k = jax.random.PRNGKey(seed)
    return (jax.random.normal(k, (B * T, HIDDEN), jnp.float32),
            jax.random.normal(jax.random.fold_in(k, 1), (B * T, HIDDEN),
                              jnp.float32))


def test_the_shares_add_up_to_the_uncut_layer():
    """The deployment's 8 shares of 8 of the 64 experts, ``held=(8j, 8)``,
    6 a token as published: their parts (there is no shared expert to count
    once) are the whole layer as the reference computes it with all 64
    held."""
    cfg = dict(tiny_cfg(held=(0, 64), routed=64),
               moe_num_active_primary_experts=6)
    w = ref.init_weights(11, cfg)
    pre = "l1.mlp/"
    m, a = _m_and_a()
    with jax.default_matmul_precision("highest"):
        whole = ref._experts(w, pre, m, a, cfg, "float32")
        total, tokens = 0.0, 0
        for first in range(0, 64, 8):
            layer = _expert_layer(cfg, (first, 8), absent="left_out")
            _, state, _ = layer.initialize(
                jax.random.PRNGKey(0), [(T, HIDDEN), (T, HIDDEN)],
                jnp.float32)
            y, state, _ = layer.apply(_expert_params(w, pre, (first, 8)),
                                      [m, a], state, train=True)
            total = total + y
            tokens += int(state["here"])
        uncut, _, _ = _expert_layer(cfg, None, absent="left_out").apply(
            _expert_params(w, pre), [m, a], {})
        # the cell's stand-in: with every expert held it changes nothing
        stand_in, _, _ = _expert_layer(cfg, None, absent="stand_in").apply(
            _expert_params(w, pre), [m, a], {})
    assert tokens == B * T * 6             # every choice is some share's
    close(total, whole)
    close(uncut, whole)
    close(stand_in, whole)


@pytest.mark.parametrize("absent", ["left_out", "stand_in"])
def test_the_stand_in_takes_the_router_off_the_absent_experts(absent):
    """The layer against the reference under both readings of the absent
    experts, output and the router's gradient. Under ``stand_in`` a token's
    output depends on the held experts' scores alone (the softmax's sum
    cancels from ``p_e / sum of the held chosen``): the columns of ``Wr``
    for the experts held elsewhere get exactly nought, and a token that
    chose no held expert gets nothing. Under ``left_out`` they get a
    gradient, the push towards the held experts that made a run's routing
    drift."""
    cfg = tiny_cfg(held=(0, 4), routed=16)
    cfg["deployment"]["absent"] = absent
    w = ref.init_weights(17, cfg)
    pre = "l1.mlp/"
    m, a = _m_and_a(2)
    ct = jax.random.normal(jax.random.PRNGKey(3), m.shape)
    layer = _expert_layer(cfg, (0, 4), absent=absent)

    def ours(wr):
        y, _, _ = layer.apply(dict(_expert_params(w, pre), Wr=wr), [m, a],
                              {})
        return jnp.sum(y * ct), y

    def theirs(wr):
        y = ref._experts(dict(w, **{pre + "Wr": wr}), pre, m, a, cfg,
                         "float32")
        return jnp.sum(y * ct)

    with jax.default_matmul_precision("highest"):
        (_, y), g = jax.value_and_grad(ours, has_aux=True)(w[pre + "Wr"])
        g_ref = jax.grad(theirs)(w[pre + "Wr"])
        top_e, _ = ref._route(w, pre, a, cfg, "float32")
    close(y, ref._experts(w, pre, m, a, cfg, "float32"))
    close(g, g_ref)
    # nought up to rounding: the softmax's sum cancels in float32 arithmetic
    absent_cols = np.abs(np.asarray(g)[:, 4:]).max() \
        / np.abs(np.asarray(g)[:, :4]).max()
    none_held = np.all(np.asarray(top_e) >= 4, axis=1)
    assert none_held.any() and not np.asarray(y)[none_held].any()
    if absent == "stand_in":
        assert absent_cols < 1e-6, absent_cols
    else:
        assert absent_cols > 1e-2, absent_cols
    with pytest.raises(ValueError, match="absent"):
        _expert_layer(cfg, (0, 4), absent="mean").apply(
            _expert_params(w, pre), [m, a], {})


def test_the_early_router_routes_on_the_attentions_input():
    """Perturb ``m`` alone and the chosen experts stay (each held expert's
    tokens are the same, the output is the reference's on the new ``m``);
    perturb ``a`` and they move. Routed on ``m`` instead (one input), the
    layer is not the reference."""
    cfg = tiny_cfg()
    w = ref.init_weights(13, cfg)
    pre = "l2.mlp/"
    layer = _expert_layer(cfg, (0, 4))
    _, state0, shape = layer.initialize(
        jax.random.PRNGKey(0), [(T, HIDDEN), (T, HIDDEN)], jnp.float32)
    assert shape == (T, HIDDEN) and layer.n_inputs == 2
    m, a = _m_and_a(1)
    m2 = m + 3.0 * jax.random.normal(jax.random.PRNGKey(9), m.shape)
    a2 = a + 3.0 * jax.random.normal(jax.random.PRNGKey(10), a.shape)
    p = _expert_params(w, pre)

    def tokens(mm, aa):
        y, state, _ = layer.apply(p, [mm, aa], state0, train=True)
        return y, np.asarray(state["tokens"])

    y1, t1 = tokens(m, a)
    y2, t2 = tokens(m2, a)
    _, t3 = tokens(m, a2)
    np.testing.assert_array_equal(t1, t2)
    assert not np.array_equal(t1, t3)
    with jax.default_matmul_precision("highest"):
        close(y2, ref._experts(w, pre, m2, a, cfg, "float32"))
        e_m, _ = ref._route(w, pre, m2, cfg, "float32")
        e_a, _ = ref._route(w, pre, a, cfg, "float32")
    assert not np.array_equal(np.asarray(e_m), np.asarray(e_a))
    own = _expert_layer(cfg, (0, 4), early_router=False)
    assert own.n_inputs == 1
    y_own, _, _ = own.apply(p, m2, {})
    assert gap(y_own, y2) > 1e-2
    with pytest.raises(ValueError, match="shape"):
        layer.initialize(jax.random.PRNGKey(0), [(T, HIDDEN), (T, 8)],
                         jnp.float32)


def test_swiglu_is_not_the_reference(world):
    """The experts' activation is the layer's: ``swish`` (the default,
    every other cell's) is not the ReGLU the reference computes."""
    cfg, w = world["cfg"], world["weights"]
    m, a = _m_and_a(2)
    with jax.default_matmul_precision("highest"):
        want = ref._experts(w, "l0.mlp/", m, a, cfg, "float32")
    relu, _, _ = _expert_layer(cfg, (0, 4)).apply(
        _expert_params(w, "l0.mlp/"), [m, a], {})
    swish, _, _ = _expert_layer(cfg, (0, 4), activation="swish").apply(
        _expert_params(w, "l0.mlp/"), [m, a], {})
    close(relu, want)
    assert gap(swish, want) > 50 * 2e-4


def _plain_experts(x, w, w1, w3, w2, top_e, activation="relu"):
    """Every token through the experts it chose, in a Python loop over the
    held experts: ``sum_e w_e (act(x W1_e) * (x W3_e)) W2_e``."""
    act = jax.nn.relu if activation == "relu" else jax.nn.silu
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w1.shape[0]):
        weight = jnp.sum(jnp.where(top_e == e, w, 0.0), axis=1)[:, None]
        h = act(x @ w1[e]) * (x @ w3[e])
        out = out + weight * (h @ w2[e])
    return out


def _scatter_add_experts(x, w, w1, w3, w2, order, ends, chunk_rows, top_k,
                         activation):
    """The held experts' sum as the expert layer once formed it: each chunk
    of the sorted rows scatter-added into its tokens (``out.at[tok].add``),
    differentiated by JAX through the gather and the scatter."""
    out = jnp.zeros(x.shape, jnp.float32)
    for c in range(-(-int(ends[-1]) // chunk_rows)):
        rows = c * chunk_rows + jnp.arange(chunk_rows)
        live = rows < ends[-1]
        a = jnp.take(order, jnp.minimum(rows, order.shape[0] - 1))
        keep = live[:, None]
        y = moe._gated(jnp.where(keep, jnp.take(x, a // top_k, axis=0), 0),
                       w1, w3, w2, moe._sizes(c, ends, chunk_rows), keep,
                       activation)
        wt = jnp.where(live, jnp.take(w.reshape(-1), a), 0.0)[:, None]
        out = out.at[a // top_k].add(y.astype(jnp.float32) * wt)
    return out


def _routing(kind, n, k, held, key):
    """``[n, k]`` distinct experts a token, some held (``0`` to ``held``) and
    some not. ``mixed``: a quarter of the tokens chose no held expert, a
    quarter one, half all ``k``; ``one_expert``: every token chose expert 0
    (and another held one); ``empty_expert``: no token chose the last held
    expert; ``all_held``: every choice is held, as with ``held=None``."""
    t = np.arange(n)[:, None]
    j = np.arange(k)[None, :]
    shift = np.asarray(jax.random.randint(key, (n, 1), 0, held))
    if kind == "mixed":
        top_e = np.where(t % 4 == 0, held + j,
                         np.where((t % 4 == 1) & (j > 0), held + j,
                                  (shift + j) % held))
    elif kind == "one_expert":
        top_e = np.where(j == 0, 0, 1 + (shift + j) % (held - 1))
    elif kind == "empty_expert":
        top_e = np.where(t % 3 == 0, held + j, (shift + j) % (held - 1))
    else:
        top_e = (shift + j) % held
    return jnp.asarray(top_e, jnp.int32)


#: (chunk rows, routing, activation, path) of 32 tokens, 2 choices each,
#: over 4 held experts; the kernel runs in interpret mode off the chip, and
#: a chunk of 24 rows does not tile it (its ``jnp`` form, ``why=shape``)
HELD_CASES = {
    "one_chunk": (64, "mixed", "relu", "jnp"),
    "chunks": (24, "mixed", "relu", "jnp"),
    "swish": (64, "mixed", "swish", "jnp"),
    "kernel": (64, "mixed", "relu", "kernel"),
    "untiled_chunks": (24, "mixed", "swish", "kernel"),
    "kernel_chunks": (16, "mixed", "swish", "kernel"),
    "one_expert_chunks": (16, "one_expert", "relu", "kernel"),
    "empty_expert": (32, "empty_expert", "swish", "kernel"),
    "all_held": (64, "all_held", "relu", "kernel"),
    "all_held_jnp": (24, "all_held", "swish", "jnp"),
}


@pytest.mark.parametrize("case", list(HELD_CASES))
def test_reglu_through_held_experts_forward_and_backward(case,
                                                         monkeypatch):
    """``held_experts(..., activation=)`` against plain ``jnp`` and against
    the scatter-add it replaced: the output and the gradients of every
    operand, in one chunk and in several, through the segment-sum kernel and
    its ``jnp`` form, for tokens with no, one and every choice held, a held
    expert no token chose, and every token on one expert; with a quarter of
    the rows at nought (``x W1`` is exactly 0 there, where relu passes no
    gradient). ``moe.combine`` counts the path once a pass."""
    from deeplearning4j_tpu.ops import flash_attention as fa
    chunk_rows, kind, activation, path = HELD_CASES[case]
    n, d, f, held, k = 32, 16, 8, 4, 2
    keys = jax.random.split(jax.random.PRNGKey(21), 6)
    x = jax.random.normal(keys[0], (n, d)).at[::4].set(0.0)
    w = jax.nn.softmax(jax.random.normal(keys[1], (n, k)), -1)
    w1, w3 = (0.3 * jax.random.normal(kk, (held, d, f)) for kk in keys[2:4])
    w2 = 0.3 * jax.random.normal(keys[4], (held, f, d))
    top_e = _routing(kind, n, k, held, keys[5])
    order, ends, tokens = moe.plan(top_e, 0, held)
    if kind == "one_expert":
        assert int(tokens[0]) == n and int(ends[-1]) > 2 * chunk_rows
    if kind == "empty_expert":
        assert int(tokens[-1]) == 0
    monkeypatch.setattr(fa, "_state", {"mode": "force" if path == "kernel"
                                       else "auto"})

    def ours(x, w, w1, w3, w2):
        return moe.held_experts(x, w, w1, w3, w2, order, ends, chunk_rows, k,
                                activation)[0]

    def plain(x, w, w1, w3, w2):
        return _plain_experts(x, w, w1, w3, w2, top_e, activation)

    def scattered(x, w, w1, w3, w2):
        return _scatter_add_experts(x, w, w1, w3, w2, order, ends,
                                    chunk_rows, k, activation)

    args = (x, w, w1, w3, w2)
    ct = jax.random.normal(jax.random.PRNGKey(5), (n, d))
    grad = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) * ct),
                               argnums=range(5))(*args)
    tiling = moe.segment_tiling(n, chunk_rows, d, 4, min(k, held))
    labels = ({"decision": "jnp", "why": "platform"} if path == "jnp" else
              {"decision": "jnp", "why": tiling} if isinstance(tiling, str)
              else {"decision": "kernel"})
    before = [_total("moe.combine", **{"pass": p}, **labels)
              for p in ("forward", "backward")]
    jax.eval_shape(lambda: grad(ours))
    assert [_total("moe.combine", **{"pass": p}, **labels)
            for p in ("forward", "backward")] == [b + 1 for b in before]
    with jax.default_matmul_precision("highest"):
        got = ours(*args)
        close(got, plain(*args), tol=1e-5)
        close(got, scattered(*args), tol=1e-5)
        g_ours, g_plain, g_scattered = grad(ours), grad(plain), grad(scattered)
    for mine, want, old in zip(g_ours, g_plain, g_scattered):
        close(mine, want, tol=1e-5)
        close(mine, old, tol=1e-5)
    if kind == "mixed":
        # the tokens that chose no held expert get an exact nought
        assert not np.asarray(got)[::4].any()
    if activation == "relu":
        # a zero row's hidden units sit at relu's zero: its x-gradient comes
        # from W3's side alone, times relu(0) = 0, so it is nought on both
        # sides
        assert not np.asarray(g_plain[0])[::4].any()
        assert not np.asarray(g_ours[0])[::4].any()


# ------------------------------------------------------ builder and counters
@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("tie_word_embeddings", True), ("moe_primary_router_apply_softmax", False),
    ("norm_topk_prob", False)])
def test_the_builder_refuses_what_it_does_not_build(key, value):
    with pytest.raises(NotImplementedError, match=key):
        smallthinker(dict(tiny_cfg(), **{key: value}), T)


def test_the_builder_refuses_layouts_of_another_depth():
    with pytest.raises(ValueError, match="rope_layout"):
        smallthinker(dict(tiny_cfg(), rope_layout=[0, 1, 1]), T)


def _total(name, **labels):
    m = tel.registry.get(name)
    if m is None:
        return 0
    return sum(v for k, v in m.series().items()
               if all((lk, lv) in k for lk, lv in labels.items()))


def test_counters_after_one_call(world):
    """``attention.dispatch`` names the rotation (``rope=none`` on the full
    layer, ``default`` on the window layers); ``moe.route`` names the
    tensor the router reads (``input=early``) and the experts' activation
    (``act=relu``); the expert counters count as in every cell, and
    ``moe.combine`` names the segment sum's path in both passes of every
    expert layer (its ``jnp`` form off the chip)."""
    cfg = world["cfg"]
    net = build(cfg, jax.tree.map(jnp.copy, world["weights"]))
    rows = np.random.default_rng(9).integers(0, cfg["vocab_size"],
                                             (2 * B, T), dtype=np.int32)
    combine0 = [_total("moe.combine", **{"pass": p}, decision="jnp",
                       why="platform") for p in ("forward", "backward")]
    kernel0 = _total("moe.combine", decision="kernel")
    nope0 = _total("attention.dispatch", kind="full", rope="none")
    win0 = _total("attention.dispatch", kind="window", rope="default")
    early0 = _total("moe.route", select="plain", scoring="softmax",
                    input="early", act="relu")
    assign0 = _total("moe.assignments")
    net.fit_on_device(rows, np.ones((2 * B, 1), np.float32), epochs=1,
                      batch_size=B)
    assert _total("attention.dispatch", kind="full", rope="none") - nope0 >= 1
    assert _total("attention.dispatch", kind="window",
                  rope="default") - win0 >= 3
    assert _total("moe.route", select="plain", scoring="softmax",
                  input="early", act="relu") - early0 >= LAYERS
    assert _total("moe.assignments") - assign0 == LAYERS * 2 * B * T * 2
    assert _total("moe.dropped") == 0
    assert all(_total("moe.combine", **{"pass": p}, decision="jnp",
                      why="platform") - c0 >= LAYERS
               for p, c0 in zip(("forward", "backward"), combine0))
    assert _total("moe.combine", decision="kernel") == kernel0


def test_the_other_cells_count_under_the_defaults():
    """The labels every other builder's sites now carry: an attention site
    that rotates as before is ``rope=default`` (or ``yarn``), a router that
    reads its own input ``input=own, act=swish``."""
    x = jax.random.normal(jax.random.PRNGKey(2), (8, HIDDEN))
    wr = jax.random.normal(jax.random.PRNGKey(3), (HIDDEN, 4))
    before = _total("moe.route", input="own", act="swish")
    moe.route(x, wr, 2, 1.0)
    assert _total("moe.route", input="own", act="swish") == before + 1
    q = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 4, 8))
    before = _total("attention.dispatch", kind="full", rope="default")
    ca.causal_attention(q, q[:, :, :2], q[:, :, :2])
    assert _total("attention.dispatch", kind="full",
                  rope="default") == before + 1
    yarn = CausalSelfAttentionLayer(n_heads=4, n_kv_heads=2, head_size=8,
                                    rope_type="yarn", rope_factor=4.0,
                                    rope_original_max_position=8)
    params, _, _ = yarn.initialize(jax.random.PRNGKey(0), (16, HIDDEN),
                                   jnp.float32)
    before = _total("attention.dispatch", rope="yarn")
    yarn.apply(params, x.reshape(1, 8, HIDDEN).repeat(2, 1), {})
    assert _total("attention.dispatch", rope="yarn") == before + 1


# ---------------------------------------------- the other builders' programs
#: sha256 of the lowered loss-and-gradient text (no debug locations) of each
#: builder's tiny stack, as the parent of the PR that brought
#: ``rope_type="none"``, ``activation`` and ``early_router`` (PR 42) lowered
#: it: the new options' defaults are that program, to the byte. A PR that
#: moves one of these programs on purpose writes its own digest here: the
#: three expert stacks' are those of the held rows going back to their
#: tokens by a segment sum.
PARENT_DIGESTS = {
    "laguna_xs2":
        "e5a0c180f4c81ebc078885925a620b90cc014199aefb94d427641be2069a6702",
    "kanana2_30b_a3b":
        "c1f098c23d6291a9056f2221bad9efe8fdfb755ece6e2be2a1fc0cbb6871a464",
    "keye_vl2_30b_a3b":
        "3ac040a47879c74fa0e90ec2aef68e87e55a6e3c02260a60af90ea4bd4243551",
    "ouro_2_6b":
        "020ddfdccf8ca29a2fc5619f0d0cc5e45525f18ba320e8e436529329c0e24d1f",
}

TINY = {
    "laguna_xs2": dict(hidden_size=32, head_dim=8, num_key_value_heads=2,
                       intermediate_size=64, moe_intermediate_size=16,
                       shared_expert_intermediate_size=16, sliding_window=4,
                       num_experts_per_tok=2, vocab_size=48,
                       num_attention_heads_per_layer=[4, 6, 6, 6, 4]),
    "kanana2_30b_a3b": dict(hidden_size=32, num_attention_heads=3,
                            qk_nope_head_dim=8, qk_rope_head_dim=6,
                            v_head_dim=7, kv_lora_rank=11,
                            intermediate_size=64, moe_intermediate_size=16,
                            num_experts_per_tok=2, vocab_size=48,
                            num_hidden_layers=3),
    "keye_vl2_30b_a3b": dict(hidden_size=32, num_attention_heads=4,
                             num_key_value_heads=2, head_dim=8,
                             moe_intermediate_size=16, num_experts_per_tok=2,
                             vocab_size=48, num_hidden_layers=3),
    "ouro_2_6b": dict(hidden_size=32, num_attention_heads=4,
                      num_key_value_heads=4, head_dim=8,
                      intermediate_size=48, vocab_size=56,
                      num_hidden_layers=2,
                      layer_types=["full_attention"] * 2, total_ut_steps=3),
}


def _lowered_digest(name, seq_len=16):
    """The cell's own configuration at toy widths (compute in bfloat16, as
    the cell), built by its ``program.build`` with recomputation as the cell
    has it; the loss and its gradient lowered on concrete arguments."""
    cfg = _config(name)
    cfg.update(TINY[name])
    if "deployment" in cfg:
        cfg["deployment"] = dict(cfg["deployment"], num_experts_routed=16,
                                 held=[0, 4])
        for key in ("num_experts", "num_local_experts", "n_routed_experts"):
            if key in cfg:
                cfg[key] = 4
    if "sa_config" in cfg:
        cfg["sa_config"] = dict(cfg["sa_config"], indexer_num_heads=3,
                                indexer_head_dim=4, topk=5)
    reference = importlib.import_module(f"benchmarks.reference.{name}")
    prog = importlib.import_module(f"benchmarks.configs.{name}.program")
    net = prog.build(cfg, reference.init_weights(0, cfg),
                     {"seq_len": seq_len})
    loss_fn = jax.value_and_grad(net._build_loss_fn(), has_aux=True)
    ids = jnp.zeros((2, seq_len), jnp.int32)
    y = jnp.ones((2, 1), jnp.float32)
    text = jax.jit(loss_fn).lower(net.params, net.state, None, (ids,), (y,),
                                  (None,), (None,)).as_text(debug_info=False)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name", list(PARENT_DIGESTS))
def test_existing_builders_lower_to_the_same_text(name):
    assert _lowered_digest(name) == PARENT_DIGESTS[name]


def test_reference_layer_in_chunks_equals_one_chunk(monkeypatch):
    """The reference's own chunking (keys and values whole, then 8
    positions at a time, a window layer's chunk over the keys its window
    reaches) changes nothing, in the NoPE full layer and a window layer."""
    cfg = tiny_cfg()
    w = ref.init_weights(17, cfg)
    h = jax.random.normal(jax.random.PRNGKey(6), (32, HIDDEN))

    def run(chunk, i):
        monkeypatch.setattr(ref, "_Q_BLOCK", chunk)
        with jax.default_matmul_precision("highest"):
            out = ref._layer(w, i, h, cfg, "float32")
            g = jax.grad(lambda w_, h_: jnp.sum(
                ref._layer(w_, i, h_, cfg, "float32") ** 2),
                argnums=(0, 1))(w, h)
        return out, g

    for i in (0, 1):
        (one, g1), (many, g2) = run(32, i), run(8, i)
        close(many, one, tol=1e-5)
        close(g2[1], g1[1], tol=1e-5)
        for leaf in g1[0]:
            if leaf.startswith(f"l{i}."):
                close(g2[0][leaf], g1[0][leaf], tol=1e-5)
