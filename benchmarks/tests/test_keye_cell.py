"""The ``keye_vl2_30b_a3b.pretrain.s8k`` cell's own files (configuration,
program, reference, traffic, entry, readers) driven through ``run_cell.run``
at a tiny size on the CPU, as ``test_kanana_cell.py`` drives its cell; the
three readers this cell brings on the scope tables of a tiny epoch under a
trace made by hand; and the expert layers' reader kept off the indexer.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests/test_keye_cell.py -q``.
"""

import copy
import json
import os
import re
import time
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import events, flops_dsa, run_cell, scopes
from benchmarks.harness import trace as tr
from benchmarks.harness.cell import BENCH_DIR, ROOT, Cell
from benchmarks.metrics import (dsa_train_mfu_pct, index_time_pct,
                                moe_load_max_over_mean, moe_time_pct,
                                sparse_attn_time_pct)

LIMITS = {"loss_gap": 1e-4, "opt_medgap_s2": 1e-2, "delta_medgap_s2": 1e-2,
          "opt_diff_s2": 5e-2, "delta_diff_s2": 5e-2}
NAME = "keye_vl2_30b_a3b.pretrain.s8k"
TINY_TRAFFIC = {"batch": 2, "seq_len": 16}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def tiny_cell(limits=None):
    cfg = _load(BENCH_DIR, "configs", "keye_vl2_30b_a3b.json")
    cfg.update(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
               head_dim=8, moe_intermediate_size=16, num_experts_per_tok=2,
               num_experts=4, num_local_experts=4, vocab_size=48,
               num_hidden_layers=3, compute_dtype="float32")
    cfg["sa_config"] = dict(cfg["sa_config"], indexer_num_heads=3,
                            indexer_head_dim=4, topk=5)
    cfg["input"] = dict(cfg["input"], vocab_size=48)
    cfg["deployment"] = dict(cfg["deployment"], num_experts_routed=16,
                             held=[0, 4])
    cfg["assumed"] = dict(cfg["assumed"], initializer_std=0.3)
    t = _load(BENCH_DIR, "traffic", "pretrain.s8k.json")
    t.update(batches=2, epochs_per_call=1, follow_steps=2, snapshots=[2],
             **TINY_TRAFFIC)
    return Cell(NAME, _load(ROOT, "BENCHMARK.json"), 1, copy.deepcopy(cfg),
                t, limits or {})


def test_keye_cell_stages():
    import deeplearning4j_tpu  # noqa: F401
    cell = tiny_cell(LIMITS)
    result = run_cell.run(cell, 2 ** 31 + 17, 0.3, False, jax.devices()[:1],
                          time.perf_counter(), events.CompileEvents())
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compiled_in_window"] == 0
    assert result["correct"], result["compared"]
    json.dumps(result)
    # the indexer's leaves are in both sides' trees and judged by no limit's
    # worst leaf: a frozen leaf reads a gap of nought
    assert result["compared"]["opt_gap_s2"]["value"] < 1e-2
    got = moe_load_max_over_mean.read({"peaks": {}, "config": cell.config})
    assert got["unit"] == "ratio" and 1.0 <= got["value"] <= 4.0


def test_the_cell_is_declared_and_finds_its_files():
    cell = Cell.load(NAME)
    assert cell.reference() and cell.program() and cell.entry()
    assert {"dsa_train_mfu_pct", "sparse_attn_time_pct", "index_time_pct",
            "moe_time_pct", "moe_load_max_over_mean", "device_idle_pct",
            "peak_hbm_pct", "fwd_time_pct", "recompute_time_pct"} <= \
        set(cell.metric_names("per_layer"))
    assert not {"train_mfu_pct", "lm_train_mfu_pct", "mla_train_mfu_pct",
                "attn_time_pct"} & set(cell.metric_names("per_layer"))
    assert cell.limits
    # every number of the catalog row is under its own key, but the four
    # the cut changes
    assert cell.config["reduced"] == ["num_hidden_layers", "num_experts",
                                      "num_local_experts", "vocab_size"]
    assert (cell.config["hidden_size"], cell.config["num_attention_heads"],
            cell.config["num_key_value_heads"], cell.config["head_dim"],
            cell.config["moe_intermediate_size"],
            cell.config["num_experts_per_tok"]) == (2048, 32, 4, 128, 768, 8)
    assert cell.config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cell.config["deployment"]["held"] == [0, 8]
    # this cell's count leaves the other decoders alone
    laguna = Cell.load("laguna_xs2.pretrain.s8k")
    ctx = {"peaks": {"flops_bf16": 1.0}, "config": laguna.config,
           "traffic": laguna.traffic, "chips": 1,
           "window": {"examples": 1, "seconds": 1.0}}
    assert dsa_train_mfu_pct.read(ctx) is None
    assert dsa_train_mfu_pct.read(dict(ctx, config=cell.config))["unit"] == "%"


def test_the_count_of_operations():
    tiny = tiny_cell().config
    T, d = 16, 32
    # by hand at the tiny size: 4 heads on 2 KV heads of 8, 3 index heads of
    # 4 against one key, the 5 best keys a query
    attn = 2 * T * d * (2 * 32 + 2 * 16)
    index = 2 * T * d * (12 + 4 + 3) + 2 * 12 * (T * (T + 1) // 2)
    open_pairs = sum(min(t + 1, 5) for t in range(T))
    scores = 4 * 32 * open_pairs
    sparse = 2 * T * d * 16 + 6 * T * d * 16 * 2 * 4 / 16
    want = 3 * (attn + index + scores + sparse) + 2 * (T - 1) * d * 48
    assert flops_dsa.forward_flops(tiny, T) == want
    assert flops_dsa.train_flops_per_example(tiny, {"seq_len": T}) == 3 * want
    cfg = _load(BENCH_DIR, "configs", "keye_vl2_30b_a3b.json")
    layer = flops_dsa.layer_forward_flops(cfg, 0, 8192)
    # the issue's count a token a layer: 37.7M, 4.5M + 8.4M, 29.4M, 4.7M
    per_token = {k: v / 8192 for k, v in layer.items()}
    assert per_token["projections"] == 2 * 18_874_368
    assert per_token["index_projections"] == 2 * 2_260_992
    assert per_token["index_scores"] == 2 * 16 * 64 * 8193 / 2
    assert per_token["scores"] == 4 * 32 * 128 * 1792.125
    assert per_token["experts"] == 6 * 2048 * 768 * 8 * 8 / 128
    assert flops_dsa.open_pairs(8192, 2048) == 14_681_088
    # the selection closes 56% of the causal pairs
    assert 0.56 < 1 - 14_681_088 / flops_dsa.causal_pairs(8192) < 0.57
    # indexer and selected attention are about half of a layer
    share = (per_token["index_projections"] + per_token["index_scores"]
             + per_token["scores"]) / sum(per_token.values())
    assert 0.48 < share < 0.52


def _fit_tiny(seq_len=32, block=8):
    """One ``fit_on_device`` call of the tiny cell at sequences four blocks
    long. -> the program's scope tables."""
    from deeplearning4j_tpu.ops import causal_attention
    from deeplearning4j_tpu.runtime import telemetry as tel
    cell = tiny_cell()
    cell.traffic.update(seq_len=seq_len)
    net = cell.program().build(
        cell.config, cell.reference().init_weights(3, cell.config),
        cell.traffic)
    ids = np.random.default_rng(1).integers(0, 48, (4, seq_len),
                                            dtype=np.int32)
    old = causal_attention.causal_attention.__kwdefaults__["block"]
    causal_attention.causal_attention.__kwdefaults__["block"] = block
    try:
        tel.reset_programs()
        net.fit_on_device(ids, np.ones((4, 1), np.float32), epochs=1,
                          batch_size=2)
        return cell, tel.program_scopes()
    finally:
        causal_attention.causal_attention.__kwdefaults__["block"] = old


def _event(name, ins, start, dur):
    return NS(name=f"%{name} = {ins['shape']}{{0}} fusion(%p0), kind=kLoop",
              start_ns=start, duration_ns=dur, stats=[])


def test_the_three_readers_on_a_tiny_traced_epoch():
    """The scope tables of a tiny epoch the program really ran, under a
    trace made by hand that gives every instruction 10 ns: the two scope
    readers find their instructions (forward and recomputed for the indexer,
    backward too for the attention), they share none, and the count reader
    divides by the peak it is given."""
    cell, tables = _fit_tiny()
    assert tables and tables[0]["site"] == "train.epoch_fn"
    table = tables[0]["instructions"]
    work = {n: i for n, i in table.items() if i["phase"] != "other"}
    evs, t = [], 0
    for name, ins in work.items():
        evs.append(_event(name, ins, t, 10))
        t += 10
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=evs)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        NS(name=tr.WINDOW_SPAN, start_ns=0, duration_ns=t, stats=[])])])
    reduced = tr.Reduced(NS(planes=[dev, host]))
    ctx = {"trace": reduced, "scopes": scopes.attribute(reduced, tables)}
    att = ctx["scopes"]
    assert att["busy"] == t and att["joined"] == t
    index = {n for n, i in work.items() if "attn.index" in i["scopes"]}
    sparse = {n for n, i in work.items() if "attn.sparse" in i["scopes"]}
    assert index and sparse and not index & sparse
    assert index_time_pct.read(ctx) == {
        "value": 100.0 * 10 * len(index) / t, "unit": "%"}
    assert sparse_attn_time_pct.read(ctx) == {
        "value": 100.0 * 10 * len(sparse) / t, "unit": "%"}
    phases = lambda names: {work[n]["phase"] for n in names}
    assert phases(index) == {"forward", "recompute"}     # no backward
    assert phases(sparse) >= {"forward", "backward"}
    # a program without such scopes (the parent's) leaves the metrics out
    bare = {"trace": reduced, "scopes": dict(att, scope={})}
    assert index_time_pct.read(bare) is None
    assert sparse_attn_time_pct.read(bare) is None
    assert index_time_pct.read({"trace": None}) is None
    full = Cell.load(NAME)
    per_s = 2.0
    got = dsa_train_mfu_pct.read({
        "peaks": {"flops_bf16": 197e12}, "config": full.config,
        "traffic": full.traffic, "chips": 1,
        "window": {"examples": 40, "seconds": 40 / per_s}})
    assert abs(got["value"] - 100 * per_s * 14.4845e12 / 197e12) < 0.01
    assert dsa_train_mfu_pct.read({"peaks": None, "config": full.config}) \
        is None


def test_the_expert_readers_patterns_catch_none_of_the_indexers_results():
    """``moe_time_pct`` finds the expert layers' fusions by result shape. At
    the cell's sizes none of its shapes is one the indexer or the selected
    attention makes (``tests/test_tpu_compile.py`` holds the indexer's
    compiled results to that); at the tiny size, where the program can be
    run here, no instruction under ``attn.index`` is caught (the tiny
    attention's ``[tokens, heads x head]`` happens to be ``[tokens, hidden]``
    and its ``[tokens, KV heads x head]`` ``[tokens, routed]``: 32 and 16
    both ways, 4,096 and 512 against 2,048 and 128 in the cell)."""
    cfg = _load(BENCH_DIR, "configs", "keye_vl2_30b_a3b.json")
    traffic = {"batch": 2, "seq_len": 8192}
    shapes = moe_time_pct.patterns(cfg, traffic)
    indexer = [
        "%fusion.1 = bf16[2,8192,1024]{2,1,0} fusion(%p0, %p1), kind=kOutput",
        "%fusion.2 = bf16[2,8192,64]{2,1,0} fusion(%p0, %p1), kind=kOutput",
        "%fusion.3 = f32[2,8192,16]{2,1,0} fusion(%p0, %p1), kind=kOutput",
        "%fusion.4 = f32[2,16,256,4096]{3,2,1,0} fusion(%p0), kind=kOutput",
        "%fusion.5 = f32[2,256,8192]{2,1,0} fusion(%p0, %p1), kind=kLoop",
        "%fusion.6 = u32[2,256,1]{2,1,0} fusion(%p0), kind=kInput",
        "%fusion.7 = pred[2,256,6144]{2,1,0} fusion(%p0), kind=kLoop",
        "%fusion.8 = pred[2,8192,8192]{2,1,0} fusion(%p0), kind=kLoop",
        "%fusion.9 = f32[8,1024,8192]{2,1,0} fusion(%p0), kind=kLoop",
        "%fusion.10 = bf16[2,8192,4096]{2,1,0} fusion(%p0), kind=kLoop"]
    assert not any(moe_time_pct.is_moe(n, shapes) for n in indexer)
    experts = [
        "%fusion.11 = f32[16384,128]{1,0} fusion(%p0), kind=kOutput",
        "%fusion.12 = f32[16384,8]{1,0} fusion(%p0), kind=kLoop",
        "%fusion.13 = s32[131072]{0} fusion(%p0), kind=kLoop",
        "%ragged-dot.3 = bf16[10240,768]{1,0} custom-call(%a, %b)"]
    assert all(moe_time_pct.is_moe(n, shapes) for n in experts)
    cell, tables = _fit_tiny()
    tiny = moe_time_pct.patterns(cell.config, cell.traffic)
    caught = [n for n, i in tables[0]["instructions"].items()
              if "attn.index" in i["scopes"] and tiny.search(i["shape"])]
    assert not caught, caught[:5]
