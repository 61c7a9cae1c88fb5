"""Which attention layers of the three decoder cells keep their output across
a recomputed segment (ISSUE 38), without the chip: each cell's own
``program.build`` on its published configuration with every width divided by
16 and the head counts as published, so that the heads' output is to the
hidden size what it is in the cell (``ouro`` 1x, ``kanana2`` 2x, ``laguna`` 3x
on its full layers and 4x on its window layers). ``ouro`` and ``kanana2`` keep
and their programs change; ``laguna`` recomputes as before and its program
lowers to the text it had, and so does ``kanana2``'s under the narrower bound
the issue names as its fallback."""

import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import memory as memmod
from deeplearning4j_tpu.nn.layers import decoder as decmod
from deeplearning4j_tpu.ops import lm_loss
from deeplearning4j_tpu.runtime import telemetry as tel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, HIDDEN = 16, 128                       # 2,048 / 16
ADAM = {"kind": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8}

#: cell -> (what to change in the published file, in its deployment, the
#: counts ``attention.kept`` grows by when the step is traced)
CELLS = {
    "laguna_xs2": (
        dict(hidden_size=HIDDEN, head_dim=8, intermediate_size=64,
             moe_intermediate_size=16, shared_expert_intermediate_size=16,
             vocab_size=48, sliding_window=4, num_experts=4,
             num_experts_per_tok=2),
        dict(num_experts_routed=16, held=[0, 4]),
        {("full", "recomputed", "wide"): 2,
         ("window", "recomputed", "wide"): 3}),
    "kanana2_30b_a3b": (
        dict(hidden_size=HIDDEN, qk_nope_head_dim=8, qk_rope_head_dim=4,
             v_head_dim=8, kv_lora_rank=32, intermediate_size=64,
             moe_intermediate_size=16, num_experts_per_tok=2,
             n_routed_experts=4, vocab_size=48, num_hidden_layers=3),
        dict(num_experts_routed=16, held=[0, 4]),
        {("latent", "kept", None): 3}),
    "ouro_2_6b": (
        dict(hidden_size=HIDDEN, head_dim=8, intermediate_size=48,
             vocab_size=50, num_hidden_layers=2,
             layer_types=["full_attention"] * 2, total_ut_steps=3),
        {},
        # two layers, traced once: the passes are one scanned body
        {("full", "kept", None): 2}),
}


def _cell(name):
    """-> (the net as the cell's ``program.build`` makes it, token ids)."""
    change, deployment, _ = CELLS[name]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{name}.json")) as f:
        cfg = json.load(f)
    cfg.update(change, compute_dtype="float32")
    cfg["deployment"] = dict(cfg["deployment"], **deployment)
    cfg["assumed"] = dict(cfg["assumed"], initializer_std=0.3, updater=ADAM)
    ref = importlib.import_module(f"benchmarks.reference.{name}")
    program = importlib.import_module(f"benchmarks.configs.{name}.program")
    net = program.build(cfg, ref.init_weights(38, cfg), {"seq_len": T})
    ids = np.random.default_rng(38).integers(0, cfg["vocab_size"], (2, T),
                                             dtype=np.int32)
    return net, ids


def _lowered(net, ids):
    loss_fn = net._build_loss_fn()
    y = jnp.ones((ids.shape[0], 1), jnp.float32)
    return jax.jit(jax.grad(lambda p: loss_fn(
        p, net.state, None, (jnp.asarray(ids),), (y,), (None,),
        (None,))[0])).lower(net.params).as_text()


def _parent_checkpoint(fn, policy, prevent_cse=True):
    """``memory.checkpoint`` as it was before anything was kept by name,
    with the barrier against CSE around every segment."""
    return jax.checkpoint(fn, policy=policy.saveable) if policy.remat else fn


def _counts():
    c = tel.registry.get("attention.kept")
    return {(kind, decision, why): c.value(
        kind=kind, decision=decision, **({"why": why} if why else {}))
        for kind in ("full", "window", "latent")
        for decision, why in (("kept", None), ("recomputed", "wide"),
                              ("recomputed", "no_policy"))}


@pytest.mark.parametrize("heads,value_width,keeps", [
    (16, 128, True),       # ouro: 2,048 wide, its hidden size
    (32, 128, True),       # kanana2: 4,096, twice
    (48, 128, False),      # laguna's full layers: 6,144, three times
    (64, 128, False)],     # laguna's window layers: 8,192, four times
    ids=["ouro", "kanana2", "laguna_full", "laguna_window"])
def test_the_rule_at_the_published_widths(heads, value_width, keeps):
    assert decmod._keeps_output(heads * value_width, 2048) is keeps


@pytest.mark.parametrize("name", list(CELLS))
def test_which_layers_of_a_cell_keep(monkeypatch, name):
    """The cell's program, recomputing a decoder layer at a time as the
    cell does: every attention site counts what the rule decides, the
    program differs from the parent's exactly where something is kept, and
    with the rule switched off (or, for ``kanana2``, narrowed to the
    fallback ``H x dv <= hidden``) it lowers to the parent's text: but for
    ``ouro``, whose segments lie in a scan's body and are checkpointed
    without the barrier against CSE since this change. The loss head's
    tags are taken out (``ops/lm_loss.py`` keeps its gradients under the
    same name in every cell), so that attention's are the only ones."""
    monkeypatch.setattr(lm_loss, "checkpoint_name", lambda a, name: a)
    net, ids = _cell(name)
    assert memmod.resolve_policy(net.conf.workspace_mode).every in (6, 8)
    before = _counts()
    text = _lowered(net, ids)
    grew = {k: v - before[k] for k, v in _counts().items() if v != before[k]}
    assert grew == CELLS[name][2]
    keeps = any(k[1] == "kept" for k in grew)
    assert ("attention.kept" in str(jax.make_jaxpr(
        lambda p: net._build_loss_fn()(
            p, net.state, None, (jnp.asarray(ids),),
            (jnp.ones((2, 1), jnp.float32),), (None,), (None,))[0])(
                net.params))) == keeps

    monkeypatch.setattr(decmod, "_keeps_output",
                        lambda width, hidden: width <= hidden)
    fallback = _lowered(net, ids)
    monkeypatch.setattr(decmod, "_keeps_output", lambda *a: False)
    unkept = _lowered(net, ids)
    monkeypatch.setattr(memmod, "checkpoint", _parent_checkpoint)
    parent = _lowered(net, ids)
    # ouro's segments lie in its run's scan and lost their CSE barriers
    # with this change: its program is new even with nothing kept
    assert (unkept == parent) == (name != "ouro_2_6b")
    assert (text == parent) == (not keeps)
    # the fallback bound keeps ouro's 1x layers and nothing of kanana2's 2x
    assert (fallback == parent) == (name != "ouro_2_6b")
    assert (fallback == text) == (name != "kanana2_30b_a3b")
    monkeypatch.setattr(
        memmod, "checkpoint", lambda fn, policy, prevent_cse=True:
        jax.checkpoint(fn, policy=policy.saveable, prevent_cse=prevent_cse))
    assert unkept == _lowered(net, ids)     # a name nothing carries: no-op


@pytest.mark.parametrize("name,layer", [
    ("laguna_xs2", "causal"), ("kanana2_30b_a3b", "causal"),
    ("ouro_2_6b", "exit_weighted")])
def test_every_cell_keeps_its_heads_gradients(name, layer):
    """The head lies in the last segment of every cell's walk: its
    gradients are formed in the forward pass and kept by name, once a traced
    step, and the gradient's program holds the logits product once (the
    forward's, the segment's and the block's own recomputation made
    three)."""
    net, ids = _cell(name)
    c = tel.registry.get("lm_head.gradients")
    before = c.value(layer=layer, decision="in_forward_kept")
    text = _lowered(net, ids)
    assert c.value(layer=layer, decision="in_forward_kept") == before + 1
    vocab = net.params["lm_head"]["W"].shape[1]
    rows = T if layer == "exit_weighted" else T - 1
    assert len(re.findall(
        rf"dot_general.*-> tensor<{rows}x{vocab}xf32>", text)) == 1
