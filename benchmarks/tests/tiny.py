"""Tiny cells for the CPU tests: the real entry points and references at
sizes a test run can hold, in float32 (on the CPU the program computes
float32 at ``highest``, so a sound run agrees with the reference closely and
the limits below sit between it and the faults)."""

import copy
import json
import os

from benchmarks.harness.cell import BENCH_DIR, ROOT, Cell


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def bench():
    return _load(ROOT, "BENCHMARK.json")


def resnet_cell(traffic_name, chips=1, limits=None, **traffic):
    cfg = _load(BENCH_DIR, "configs", "resnet50.json")
    cfg.update(image_size=32, num_classes=10, compute_dtype="float32")
    cfg["input"]["image_size"] = 32
    cfg["labels"]["classes"] = 10
    tr = _load(BENCH_DIR, "traffic", traffic_name + ".json")
    tr.update(batch=16 * chips, batches=2, chips=chips)
    if tr["entry"] == "fit_on_device":
        tr.update(epochs_per_call=1, 
                  follow_steps=2, snapshots=[2])
    else:
        tr.update(follow_steps=2, snapshots=[1, 2])
    tr.update(traffic)
    return Cell("resnet50." + traffic_name, bench(), chips, cfg, tr,
                limits or {})


def bert_cell(limits=None):
    cfg = _load(BENCH_DIR, "configs", "bert_base.json")
    cfg.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=2,
               intermediate_size=128, vocab_size=100,
               max_position_embeddings=32, graph_dir="tests/data",
               compute_dtype="float32")
    cfg["input"] = dict(cfg["input"], vocab_size=100)
    tr = _load(BENCH_DIR, "traffic", "finetune.s512.json")
    tr.update(batch=4, seq_len=16, batches=4)
    return Cell("bert_base.finetune.s512", bench(), 1, copy.deepcopy(cfg),
                tr, limits or {})
