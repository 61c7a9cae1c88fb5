"""The ``laguna_xs2.pretrain.s8k`` cell's own files (configuration, program,
reference, traffic, entry, readers) driven through ``run_cell.run`` at a
tiny size on the CPU, as ``test_harness.py`` drives the others.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests/test_laguna_cell.py -q``.
"""

import copy
import json
import os
import time

import jax

from benchmarks.harness import events, flops_lm, run_cell
from benchmarks.harness.cell import BENCH_DIR, ROOT, Cell
from benchmarks.metrics import moe_load_max_over_mean, moe_time_pct

LIMITS = {"loss_gap": 1e-4, "opt_medgap_s2": 1e-2, "delta_medgap_s2": 1e-2,
          "opt_diff_s2": 5e-2, "delta_diff_s2": 5e-2}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def tiny_cell(limits=None):
    cfg = _load(BENCH_DIR, "configs", "laguna_xs2.json")
    cfg.update(hidden_size=32, head_dim=8, num_key_value_heads=2,
               intermediate_size=64, moe_intermediate_size=16,
               shared_expert_intermediate_size=16, sliding_window=4,
               num_experts_per_tok=2, num_experts=4, vocab_size=48,
               num_attention_heads_per_layer=[4, 6, 6, 6, 4],
               compute_dtype="float32")
    cfg["input"] = dict(cfg["input"], vocab_size=48)
    cfg["deployment"] = dict(cfg["deployment"], num_experts_routed=16,
                             held=[0, 4])
    cfg["assumed"] = dict(cfg["assumed"], initializer_std=0.3)
    tr = _load(BENCH_DIR, "traffic", "pretrain.s8k.json")
    tr.update(seq_len=16, batches=2, epochs_per_call=1, follow_steps=2,
              snapshots=[2])
    return Cell("laguna_xs2.pretrain.s8k", _load(ROOT, "BENCHMARK.json"), 1,
                copy.deepcopy(cfg), tr, limits or {})


def test_laguna_cell_stages():
    import deeplearning4j_tpu  # noqa: F401
    cell = tiny_cell(LIMITS)
    result = run_cell.run(cell, 2 ** 31 + 17, 0.3, False, jax.devices()[:1],
                          time.perf_counter(), events.CompileEvents())
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compiled_in_window"] == 0
    assert result["correct"], result["compared"]
    json.dumps(result)
    # the counters the load reader reads are there after the drive
    got = moe_load_max_over_mean.read({"peaks": {}, "config": cell.config})
    assert got["unit"] == "ratio" and 1.0 <= got["value"] <= 4.0


def test_the_cell_is_declared_and_finds_its_files():
    cell = Cell.load("laguna_xs2.pretrain.s8k")
    assert cell.reference() and cell.program() and cell.entry()
    assert {"lm_train_mfu_pct", "moe_time_pct", "moe_load_max_over_mean",
            "device_idle_pct", "peak_hbm_pct"} <= \
        set(cell.metric_names("per_layer"))
    assert "train_mfu_pct" not in cell.metric_names("per_layer")
    assert cell.limits


def test_the_count_of_operations():
    cfg = _load(BENCH_DIR, "configs", "laguna_xs2.json")
    assert flops_lm.open_pairs(8192) == 8192 * 8193 // 2
    assert flops_lm.open_pairs(8192, 512) == 512 * 513 // 2 + 7680 * 512
    full = flops_lm.layer_forward_flops(cfg, 0, 8192)
    win = flops_lm.layer_forward_flops(cfg, 1, 8192)
    # a full layer's scores cost 8x a window layer's per head at 8,192 tokens
    per_head = (full["scores"] / 48) / (win["scores"] / 64)
    assert 8.0 < per_head < 8.5
    assert win["experts"] == 6.0 * 8192 * 2048 * 512 * 8 * 16 / 256
    step = 2 * flops_lm.train_flops_per_example(cfg, {"seq_len": 8192})
    assert 40e12 < step < 52e12          # the issue's estimate: about 46 TFLOP


def test_moe_operations_are_told_from_the_rest():
    cfg = _load(BENCH_DIR, "configs", "laguna_xs2.json")
    shapes = moe_time_pct.patterns(cfg, {"batch": 2, "seq_len": 8192})
    yes = ["%ragged-dot-none.1 = bf16[10240,512]{1,0} custom-call(%a, %b), "
           'custom_call_target="tpu_custom_call"',
           "%fusion.7 = bf16[10240,2048]{1,0} fusion(%p0, %p1), kind=kLoop",
           "%sort.3 = (s32[131072]{0}, s32[131072]{0}) sort(%a, %b)",
           "%fusion.9 = f32[16384,256]{1,0} fusion(%p0), kind=kOutput",
           "%scatter.2 = f32[16384,2048]{1,0} scatter(%a, %b, %c)"]
    no = ["%fusion.1 = bf16[2,8192,2048]{2,1,0} fusion(%p0), kind=kLoop",
          "%fusion.2 = (f32[16,2048,512]{2,1,0}, bf16[16,2048,512]{2,1,0}) "
          "fusion(%p0, %p1), kind=kLoop",
          "%while.4 = (s32[], f32[16384,2048]{1,0}, s32[131072]{0}) "
          "while(%tuple.1), condition=%c, body=%b"]
    assert all(moe_time_pct.is_moe(n, shapes) for n in yes)
    assert not any(moe_time_pct.is_moe(n, shapes) for n in no)
    assert moe_time_pct.patterns({"num_experts_per_tok": 1}, {}) is None
