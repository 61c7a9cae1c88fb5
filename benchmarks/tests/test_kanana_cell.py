"""The ``kanana2_30b_a3b.pretrain.s8k`` cell's own files (configuration,
program, reference, traffic, entry, readers) driven through ``run_cell.run``
at a tiny size on the CPU, as ``test_laguna_cell.py`` drives its cell.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests/test_kanana_cell.py -q``.
"""

import copy
import json
import os
import re
import time

import jax
import jax.numpy as jnp

from benchmarks.harness import events, flops_mla, run_cell
from benchmarks.harness.cell import BENCH_DIR, ROOT, Cell
from benchmarks.metrics import (mla_time_pct, mla_train_mfu_pct,
                                moe_load_max_over_mean, moe_time_pct)
from benchmarks.tests import test_laguna_cell

LIMITS = {"loss_gap": 1e-4, "opt_medgap_s2": 1e-2, "delta_medgap_s2": 1e-2,
          "opt_diff_s2": 5e-2, "delta_diff_s2": 5e-2}
NAME = "kanana2_30b_a3b.pretrain.s8k"
TINY_TRAFFIC = {"batch": 2, "seq_len": 16}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def tiny_cell(limits=None):
    cfg = _load(BENCH_DIR, "configs", "kanana2_30b_a3b.json")
    cfg.update(hidden_size=32, num_attention_heads=3, qk_nope_head_dim=8,
               qk_rope_head_dim=6, v_head_dim=7, kv_lora_rank=11,
               intermediate_size=64, moe_intermediate_size=16,
               num_experts_per_tok=2, n_routed_experts=4, vocab_size=48,
               num_hidden_layers=3, compute_dtype="float32")
    cfg["input"] = dict(cfg["input"], vocab_size=48)
    cfg["deployment"] = dict(cfg["deployment"], num_experts_routed=16,
                             held=[0, 4])
    cfg["assumed"] = dict(cfg["assumed"], initializer_std=0.3,
                          select_bias_std=0.1)
    tr = _load(BENCH_DIR, "traffic", "pretrain.s8k.json")
    tr.update(batches=2, epochs_per_call=1, follow_steps=2, snapshots=[2],
              **TINY_TRAFFIC)
    return Cell(NAME, _load(ROOT, "BENCHMARK.json"), 1, copy.deepcopy(cfg),
                tr, limits or {})


def test_kanana_cell_stages():
    import deeplearning4j_tpu  # noqa: F401
    cell = tiny_cell(LIMITS)
    result = run_cell.run(cell, 2 ** 31 + 17, 0.3, False, jax.devices()[:1],
                          time.perf_counter(), events.CompileEvents())
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compiled_in_window"] == 0
    assert result["correct"], result["compared"]
    json.dumps(result)
    # the selection bias is in both sides' trees and judged by no limit's
    # worst leaf: a frozen leaf reads a gap of nought
    assert result["compared"]["opt_gap_s2"]["value"] < 1e-2
    got = moe_load_max_over_mean.read({"peaks": {}, "config": cell.config})
    assert got["unit"] == "ratio" and 1.0 <= got["value"] <= 4.0


def test_the_cell_is_declared_and_finds_its_files():
    cell = Cell.load(NAME)
    assert cell.reference() and cell.program() and cell.entry()
    assert {"mla_train_mfu_pct", "mla_time_pct", "moe_time_pct",
            "moe_load_max_over_mean", "device_idle_pct", "peak_hbm_pct"} <= \
        set(cell.metric_names("per_layer"))
    assert not {"train_mfu_pct", "lm_train_mfu_pct"} & \
        set(cell.metric_names("per_layer"))
    assert cell.limits
    # every number of the catalog row is under its own key, but the three
    # the cut changes
    assert cell.config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                      "vocab_size"]
    assert (cell.config["qk_nope_head_dim"], cell.config["qk_rope_head_dim"],
            cell.config["v_head_dim"], cell.config["kv_lora_rank"]) == \
        (128, 64, 128, 512)
    # the readers of the cells the benchmark had leave this one alone, and
    # this one's leave theirs alone
    laguna = Cell.load("laguna_xs2.pretrain.s8k")
    assert mla_time_pct.shape_patterns(laguna.config, laguna.traffic) is None
    ctx = {"peaks": {"flops_bf16": 1.0}, "config": laguna.config,
           "traffic": laguna.traffic, "chips": 1,
           "window": {"examples": 1, "seconds": 1.0}}
    assert mla_train_mfu_pct.read(ctx) is None


def test_the_count_of_operations():
    tiny = tiny_cell().config
    T, d, H = 16, 32, 3
    # by hand at the tiny size: the four matrices, scores over 14 channels
    # and values over 7 under a causal mask, the feed-forwards, the head
    matrices = d * H * 14 + d * (11 + 6) + 11 * H * (8 + 7) + H * 7 * d
    attn = 2 * T * matrices + 2 * H * (14 + 7) * (T * (T + 1) // 2)
    dense = 6 * T * d * 64
    sparse = 2 * T * d * 16 + 6 * T * d * 32 + 6 * T * d * 16 * 2 * 4 / 16
    want = 3 * attn + dense + 2 * sparse + 2 * (T - 1) * d * 48
    assert flops_mla.forward_flops(tiny, T) == want
    assert flops_mla.train_flops_per_example(tiny, {"seq_len": T}) == 3 * want
    cfg = _load(BENCH_DIR, "configs", "kanana2_30b_a3b.json")
    layer = flops_mla.layer_forward_flops(cfg, 1, 8192)
    assert layer["projections"] == 2.0 * 8192 * (
        12582912 + 1179648 + 4194304 + 8388608)
    assert layer["scores"] == 2.0 * 32 * 320 * (8192 * 8193 // 2)
    assert layer["experts"] == 6.0 * 8192 * 2048 * 768 * 6 * 8 / 128
    # the issue's count: 8.81 TFLOP forward a sequence, attention 76% of it
    forward = flops_mla.forward_flops(cfg, 8192)
    assert 8.80e12 < forward < 8.82e12
    share = cfg["num_hidden_layers"] * (layer["projections"]
                                        + layer["scores"]) / forward
    assert 0.75 < share < 0.77


_NO_WORK = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")


def _hlo_results(fn, *args):
    """[(result shapes, whole line)] of a compiled program's instructions
    that do work (a parameter or a tuple runs nothing and names no scope)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    out = []
    for line in text.split("\n"):
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\(?[a-z0-9]+\[[^=]*?) "
                     r"([a-z\-]+)\(", line)
        if m and m.group(2) not in _NO_WORK:
            out.append((m.group(1), line))
    return out


def test_mla_operations_are_told_from_the_rest():
    cfg = _load(BENCH_DIR, "configs", "kanana2_30b_a3b.json")
    shapes = mla_time_pct.shape_patterns(cfg, {"batch": 2, "seq_len": 8192})
    yes = ["%fusion.7 = bf16[1,1024,192]{2,1,0} fusion(%p0, %p1), kind=kLoop",
           "%fusion.9 = f32[1024]{0} fusion(%p0), kind=kInput",
           "%fusion.11 = bf16[1,8192,128]{2,1,0} fusion(%p0, %p1), kind=kOutput",
           "%fusion.12 = bf16[64,7168,128]{2,1,0} fusion(%p0), kind=kLoop",
           "%fusion.13 = bf16[2,8192,576]{2,1,0} fusion(%p0, %p1), kind=kOutput",
           "%fusion.14 = bf16[2,8192,8192]{2,1,0} fusion(%p0, %p1), kind=kOutput",
           "%fusion.15 = f32[2,8192,32,32,2]{4,3,2,1,0} fusion(%p0), kind=kLoop",
           "%fusion.16 = (f32[512,8192]{1,0}, f32[]) fusion(%p0, %p1), kind=kOutput",
           "%copy.3 = bf16[2,32,8192,128]{3,2,1,0} copy(%p0)",
           "%fusion.17 = bf16[2,8192,4096]{2,1,0} fusion(%p0), kind=kLoop"]
    no = ["%fusion.1 = bf16[2,8192,2048]{2,1,0} fusion(%p0), kind=kLoop",
          "%fusion.2 = bf16[2,8192,6144]{2,1,0} fusion(%p0, %p1), kind=kOutput",
          "%fusion.3 = f32[2,8192]{1,0} fusion(%p0), kind=kInput",
          "%fusion.4 = f32[16384,128]{1,0} fusion(%p0), kind=kOutput",
          "%fusion.5 = bf16[7680,768]{1,0} fusion(%p0, %p1), kind=kLoop",
          "%fusion.6 = f32[8191,16032]{1,0} fusion(%p0), kind=kLoop",
          "%while.4 = (s32[], bf16[64,8192,192]{2,1,0}) while(%tuple.1), "
          "condition=%c, body=%b"]
    assert all(mla_time_pct.is_mla(n, shapes) for n in yes)
    assert not any(mla_time_pct.is_mla(n, shapes) for n in no)
    # the expert layers' reader and this one claim nothing of each other's
    moe = moe_time_pct.patterns(cfg, {"batch": 2, "seq_len": 8192})
    assert not any(moe_time_pct.is_moe(n, moe) for n in yes)
    assert mla_time_pct.shape_patterns({"hidden_size": 8}, TINY_TRAFFIC) is None


def _weight_shapes(cfg):
    """Result shapes of the layer's own leaves that the patterns claim: the
    weight-gradient products, the master-to-compute cast and the updater's
    sweep make them outside the layer's scopes."""
    d, H, rank = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return [f"[{d},{rank + rope}]", f"[{rank},{H * (nope + vd)}]",
            f"[{H * vd},{d}]", f"[{rank}]"]


def test_mla_patterns_meet_the_blocked_program(monkeypatch):
    """The whole compiled epoch (forward, recomputation, backward, updater)
    at a tiny size whose sequences are four blocks long, so that the blocked
    path's patterns (row statistics, key blocks alone and stacked) meet the
    program's own HLO and its metadata: what they catch names the layer's
    scopes, but for the weight-shaped results listed in ``_weight_shapes``
    and the compiler's own fills and moves, which name no primitive;
    they catch the blocked rows' statistics; and nothing of laguna's."""
    from deeplearning4j_tpu.ops import causal_attention
    monkeypatch.setitem(causal_attention.causal_attention.__kwdefaults__,
                        "block", 8)
    cell = tiny_cell()
    cell.traffic.update(seq_len=32)
    B, T = cell.traffic["batch"], cell.traffic["seq_len"]
    tiny = mla_time_pct.shape_patterns(cell.config, cell.traffic)
    assert tiny.search("f32[8]") and not mla_time_pct.shape_patterns(
        cell.config, dict(cell.traffic, seq_len=8)).search("f32[8]")

    def epoch_rows(c):
        net = c.program().build(
            c.config, c.reference().init_weights(3, c.config), c.traffic)
        return _hlo_results(
            net._build_epoch_fn(), net.params, net.updater_state, net.state,
            net._ensure_sentinel(), jnp.int32(0), jax.random.PRNGKey(0),
            (jnp.zeros((2, B, T), jnp.int32),),
            (jnp.ones((2, B, 1), jnp.float32),))

    rows = epoch_rows(cell)
    weights = _weight_shapes(cell.config)
    caught = [(r, line) for r, line in rows if tiny.search(r)]
    # the compiler's own fills and moves name no primitive of the program's:
    # no metadata, or a name that ends at the loop's frame
    own = re.compile(r'op_name="[^"]*/(?!closed_call"|body")[^/"]+"')
    stray = [line[:300] for r, line in caught if "attn.latent" not in line
             and not any(w in r for w in weights) and own.search(line)]
    assert caught and not stray, stray[:5]
    # the blocked rows ran: their statistics and their key blocks are there
    assert any(re.search(r"f32\[(1,)?8(,1)?\]", r) for r, _ in caught)
    assert any(re.search(r"\[(6,)?(8|24),7\]", r) for r, _ in caught)
    named = [r for r, line in rows if "attn.latent" in line]
    assert len([r for r in named if tiny.search(r)]) >= len(named) // 3

    lag = test_laguna_cell.tiny_cell()
    lag.traffic.update(seq_len=32)
    assert not [r for r, _ in epoch_rows(lag) if tiny.search(r)]
