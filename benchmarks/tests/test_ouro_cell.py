"""The ``ouro_2_6b.pretrain.s8k`` cell's own files (configuration, program,
reference, traffic, entry, readers) driven through ``run_cell.run`` at a
tiny size on the CPU, as ``test_kanana_cell.py`` drives its cell.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests/test_ouro_cell.py -q``.
"""

import copy
import json
import os
import re
import time

import jax
import jax.numpy as jnp

from benchmarks.harness import events, flops_loop, run_cell
from benchmarks.harness.cell import BENCH_DIR, ROOT, Cell
from benchmarks.metrics import (lm_train_mfu_pct, loop_head_time_pct,
                                loop_train_mfu_pct, mla_time_pct)
from benchmarks.tests import test_kanana_cell

LIMITS = {"loss_gap": 1e-4, "opt_medgap_s2": 1e-2, "delta_medgap_s2": 1e-2,
          "opt_diff_s2": 5e-2, "delta_diff_s2": 5e-2}
NAME = "ouro_2_6b.pretrain.s8k"


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def tiny_cell(limits=None):
    cfg = _load(BENCH_DIR, "configs", "ouro_2_6b.json")
    cfg.update(hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
               head_dim=8, intermediate_size=48, vocab_size=56,
               num_hidden_layers=2, layer_types=["full_attention"] * 2,
               total_ut_steps=3, compute_dtype="float32")
    cfg["input"] = dict(cfg["input"], vocab_size=56)
    cfg["assumed"] = dict(cfg["assumed"], initializer_std=0.3,
                          exit_gate_std=0.3)
    tr = _load(BENCH_DIR, "traffic", "pretrain.s8k.json")
    tr.update(seq_len=16, batches=2, epochs_per_call=1, follow_steps=2,
              snapshots=[2])
    return Cell(NAME, _load(ROOT, "BENCHMARK.json"), 1, copy.deepcopy(cfg),
                tr, limits or {})


def test_ouro_cell_stages():
    import deeplearning4j_tpu  # noqa: F401
    from deeplearning4j_tpu.runtime import telemetry as tel
    cell = tiny_cell(LIMITS)
    result = run_cell.run(cell, 2 ** 31 + 17, 0.3, False, jax.devices()[:1],
                          time.perf_counter(), events.CompileEvents())
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compiled_in_window"] == 0
    assert result["correct"], result["compared"]
    json.dumps(result)
    # every leaf of the reference is a leaf of the program, the shared ones
    # once: nothing is left out of the comparison
    assert result["compared"]["opt_gap_s2"]["value"] < 1e-2
    # the head's counter is there after the drive (``loop.passes`` carries
    # the graph's label and goes when the run releases the graph)
    mass = tel.snapshot()["loop.exit_mass"]["series"]
    assert len(mass) == cell.config["total_ut_steps"]


def test_the_cell_is_declared_and_finds_its_files():
    cell = Cell.load(NAME)
    assert cell.reference() and cell.program() and cell.entry()
    assert cell.chips == 1 and cell.traffic["name"] == "pretrain.s8k"
    assert {"loop_train_mfu_pct", "loop_head_time_pct", "device_idle_pct",
            "peak_hbm_pct", "input_exposed_pct", "step_host_exposed_pct",
            "idle_unattributed_pct"} == set(cell.metric_names("per_layer"))
    assert set(cell.limits) == {"loss_gap", "opt_medgap_s4",
                                "delta_medgap_s4", "opt_diff_s4",
                                "delta_diff_s4"}
    # every number of the catalog row is under its own key, but the two the
    # cut changes; the widths, the vocabulary and the passes as published
    cfg = cell.config
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["total_ut_steps"], cfg["early_exit_threshold"]) == \
        (2048, 16, 16, 128, 5632, 49152, 4, 1)
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 4
    assert cfg["published"]["num_hidden_layers"] == 48
    for key in ("exit_gate", "exit_beta", "objective", "norms", "pass_norm",
                "rotary", "updater", "initializer_std", "labels"):
        assert key in cfg["assumed"], key
    # the readers of the cells the benchmark had leave this one alone, and
    # this one's leave theirs alone
    for other in ("laguna_xs2.pretrain.s8k", "kanana2_30b_a3b.pretrain.s8k"):
        c = Cell.load(other)
        assert loop_head_time_pct.shape_patterns(c.config, c.traffic) is None
        ctx = {"peaks": {"flops_bf16": 1.0}, "config": c.config,
               "traffic": c.traffic, "chips": 1,
               "window": {"examples": 1, "seconds": 1.0}}
        assert loop_train_mfu_pct.read(ctx) is None
    assert mla_time_pct.shape_patterns(cfg, cell.traffic) is None
    assert "lm_train_mfu_pct" not in cell.metric_names("per_layer")
    assert lm_train_mfu_pct.read is not None


def test_the_count_of_operations():
    """``flops_loop`` against a count made another way: every matrix the
    reference's table holds, by its shape, walked as often as it is used."""
    from benchmarks.reference import ouro_2_6b as ref
    for cfg, T in ((tiny_cell().config, 16),
                   (_load(BENCH_DIR, "configs", "ouro_2_6b.json"), 8192)):
        R, L = cfg["total_ut_steps"], cfg["num_hidden_layers"]
        H, hd = cfg["num_attention_heads"], cfg["head_dim"]
        want = 0.0
        for name, shape, _ in ref.layer_table(cfg):
            if len(shape) != 2 or name == "embed/W":
                continue                      # gains; a look-up is no product
            positions = T - 1 if name.startswith("lm_head/") else T
            want += R * 2.0 * positions * shape[0] * shape[1]
        pairs = sum(i + 1 for i in range(T))  # keys open to query i
        want += R * L * H * pairs * (2.0 * hd + 2.0 * hd)
        assert abs(flops_loop.forward_flops(cfg, T) - want) <= 1e-9 * want
        assert flops_loop.train_flops_per_example(cfg, {"seq_len": T}) == \
            3 * flops_loop.forward_flops(cfg, T)
    # the issue's count at the cell's sizes, for a step of 2 sequences
    layer = flops_loop.layer_forward_flops(cfg, 8192)
    head = flops_loop.head_forward_flops(cfg, 8192)
    assert 0.54e12 < 2 * layer["projections"] < 0.56e12
    assert 0.54e12 < 2 * layer["scores"] < 0.56e12
    assert 1.13e12 < 2 * layer["feed_forward"] < 1.14e12
    assert 3.29e12 < 2 * head["head"] < 3.31e12
    step = 2 * flops_loop.train_flops_per_example(cfg, {"seq_len": 8192})
    assert 146e12 < step < 148e12
    assert 0.26 < 3 * 4 * 2 * head["head"] / step < 0.28
    ctx = {"peaks": {"flops_bf16": 197e12}, "config": cfg,
           "traffic": {"seq_len": 8192}, "chips": 1,
           "window": {"examples": 2, "seconds": 1.0}}
    got = loop_train_mfu_pct.read(ctx)
    assert got["unit"] == "%" and 74.0 < got["value"] < 75.5


def test_head_operations_are_told_from_the_rest(monkeypatch):
    from deeplearning4j_tpu.nn.layers import decoder
    cfg = _load(BENCH_DIR, "configs", "ouro_2_6b.json")
    traffic = {"batch": 2, "seq_len": 8192}
    # a block as wide as the hidden size: only the vocabulary tells
    C = 2048
    monkeypatch.setattr(decoder, "LM_HEAD_BLOCK", C)
    shapes = loop_head_time_pct.shape_patterns(cfg, traffic)
    yes = [f"%fusion.7 = f32[{C},49152]{{1,0}} fusion(%p0, %p1), kind=kOutput",
           f"%fusion.8 = bf16[{C},49152]{{1,0}} fusion(%p0), kind=kLoop",
           "%fusion.9 = (f32[], bf16[2048,49152]{1,0}) fusion(%p0, %p1), "
           "kind=kOutput",
           "%fusion.10 = (f32[2048,49152]{1,0}, bf16[2048,49152]{1,0}, "
           "f32[2048,49152]{1,0}) fusion(%p0), kind=kLoop"]
    no = ["%fusion.1 = bf16[2,8192,2048]{2,1,0} fusion(%p0), kind=kLoop",
          "%fusion.2 = bf16[2,8192,5632]{2,1,0} fusion(%p0, %p1), kind=kOutput",
          "%fusion.3 = f32[2,8192]{1,0} fusion(%p0), kind=kInput",
          "%fusion.4 = f32[49152,2048]{1,0} fusion(%p0), kind=kLoop",
          "%fusion.5 = f32[1024]{0} fusion(%p0), kind=kInput",
          "%fusion.6 = bf16[2048,2048]{1,0} fusion(%p0, %p1), kind=kOutput",
          "%fusion.11 = f32[4,2,8192]{2,1,0} fusion(%p0), kind=kLoop",
          # the top lines of the other decoder cells' traces (PERF.md 5)
          "%fusion.12 = bf16[1,1024,192]{2,1,0} fusion(%p0), kind=kLoop",
          "%fusion.13 = bf16[1,8192,128]{2,1,0} fusion(%p0), kind=kOutput",
          "%fusion.14 = bf16[64,8192,192]{2,1,0} fusion(%p0), kind=kLoop",
          "%fusion.15 = bf16[16384,2048]{1,0} fusion(%p0), kind=kLoop",
          "%fusion.16 = bf16[2,8192,6144]{2,1,0} fusion(%p0), kind=kOutput",
          "%fusion.17 = f32[6,1024]{1,0} fusion(%p0), kind=kInput",
          "%fusion.18 = f32[1,1024,4096]{2,1,0} fusion(%p0), kind=kLoop",
          "%custom-call.3 = bf16[96,8192,128]{2,1,0} custom-call(%p0)",
          f"%while.4 = (s32[], f32[{C},49152]{{1,0}}) while(%tuple.1), "
          "condition=%c, body=%b"]
    assert all(loop_head_time_pct.is_head(n, shapes) for n in yes)
    assert not any(loop_head_time_pct.is_head(n, shapes) for n in no)
    # a block that is no other width is claimed too: its hidden states,
    # their cotangent and its row statistics
    monkeypatch.setattr(decoder, "LM_HEAD_BLOCK", 4096)
    wide = loop_head_time_pct.shape_patterns(cfg, traffic)
    own = ["%fusion.1 = bf16[4096,2048]{1,0} fusion(%p0, %p1), kind=kOutput",
           "%fusion.2 = f32[4096]{0} fusion(%p0), kind=kInput",
           "%fusion.3 = bf16[16,4096,2048]{2,1,0} fusion(%p0), kind=kLoop"]
    assert all(loop_head_time_pct.is_head(n, wide) for n in own + yes[2:])
    assert not any(loop_head_time_pct.is_head(n, shapes) for n in own)
    assert not any(loop_head_time_pct.is_head(n, wide) for n in no[:-1])
    # a vocabulary as wide as another layer cannot be told apart
    assert loop_head_time_pct.shape_patterns(
        dict(cfg, vocab_size=5632), traffic) is None


def test_head_patterns_meet_the_compiled_program(monkeypatch):
    """The whole compiled epoch (forward, recomputation, backward, updater)
    at a tiny size whose sequences are eight head blocks long: what the
    patterns catch names the head's scope in its metadata, but for the
    weight-shaped results (the weight gradient's sum, the cast and the
    updater's sweep lie outside the scope) and the compiler's own fills and
    moves, which name no primitive; the head's blocks are among the caught;
    and most of what names the scope is caught."""
    from deeplearning4j_tpu.nn.layers import decoder
    monkeypatch.setattr(decoder, "LM_HEAD_BLOCK", 4)
    cell = tiny_cell()
    cell.traffic.update(seq_len=32)
    B, T = cell.traffic["batch"], cell.traffic["seq_len"]
    d, V = cell.config["hidden_size"], cell.config["vocab_size"]
    tiny = loop_head_time_pct.shape_patterns(cell.config, cell.traffic)
    assert tiny.search(f"f32[4,{V}]") and tiny.search("f32[4]") \
        and tiny.search(f"f32[48,4,{d}]")

    def epoch_rows(c):
        net = c.program().build(
            c.config, c.reference().init_weights(3, c.config), c.traffic)
        return test_kanana_cell._hlo_results(
            net._build_epoch_fn(), net.params, net.updater_state, net.state,
            net._ensure_sentinel(), jnp.int32(0), jax.random.PRNGKey(0),
            (jnp.zeros((2, B, T), jnp.int32),),
            (jnp.ones((2, B, 1), jnp.float32),))

    rows = epoch_rows(cell)
    weights = [f"[{d},{V}]"]
    caught = [(r, line) for r, line in rows if tiny.search(r)]
    own = re.compile(r'op_name="[^"]*/(?!closed_call"|body")[^/"]+"')
    stray = [line[:300] for r, line in caught if "lm_head." not in line
             and not any(w in r for w in weights) and own.search(line)]
    assert caught and not stray, stray[:5]
    assert any(re.search(rf"f32\[4,{V}\]", r) for r, _ in caught)
    named = [r for r, line in rows if "lm_head.passes" in line]
    assert len([r for r in named if tiny.search(r)]) >= len(named) // 2
    # the repeated run's scope is in the program too
    assert any("loop.pass" in line for _, line in rows)
