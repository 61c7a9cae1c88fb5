"""The program's side of the ``bert_base`` configuration, through the path
every record of this model took: frozen TF GraphDef ->
``TensorflowFrameworkImporter.import_graph_def(trainable=True)`` ->
``fuse_attention`` -> mean-pool head -> ``set_dtype("BFLOAT16")`` ->
``SameDiff.fit``. The graph file holds structure only; every VARIABLE is set
here from the benchmark's weights, under the reference's leaf names."""

from __future__ import annotations

import gzip
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
INPUT, OUTPUT = "ids", "Identity"       # as tools/freeze_bert.py printed

_LAYER = re.compile(r"encoder/layer_\._(\d+)/(.+)$")
_PARTS = {
    "attention/self/query": "q", "attention/self/key": "k",
    "attention/self/value": "v", "attention/output/dense": "attn_out",
    "intermediate/dense": "ffn_in", "output/dense": "ffn_out",
}
_NORMS = {"attention/output/LayerNorm": "attn_ln", "output/LayerNorm": "ffn_ln"}


def reference_name(tf_name: str):
    """The reference's leaf for one imported VARIABLE, or None (the pooler)."""
    if tf_name in ("cls_W", "cls_b"):
        return "cls/" + tf_name[-1]
    body = tf_name.split("tf_bert_model/bert/", 1)[-1]
    body = body.rsplit("/resource", 1)[0]

    def norm(rest, prefix):
        if rest.endswith("batchnorm/mul/ReadVariableOp"):
            return prefix + "_gamma"
        if rest.endswith("batchnorm/ReadVariableOp"):
            return prefix + "_beta"
        raise KeyError(tf_name)

    if body.startswith("embeddings/"):
        rest = body[len("embeddings/"):]
        table = {"Gather": "word", "Gather_1": "position",
                 "Gather_2": "token_type"}
        if rest in table:
            return "embeddings/" + table[rest]
        return "embeddings/" + norm(rest, "ln")
    if body.startswith("pooler/"):
        return None
    m = _LAYER.match(body)
    if not m:
        raise KeyError(tf_name)
    layer, rest = f"layer{m.group(1)}", m.group(2)
    for part, short in _NORMS.items():
        if rest.startswith(part + "/"):
            return f"{layer}/" + norm(rest, short)
    for part, short in _PARTS.items():
        if rest.startswith(part + "/"):
            kind = "b" if "BiasAdd" in rest else "W"
            return f"{layer}/{short}_{kind}"
    raise KeyError(tf_name)


def build(cfg: dict, weights: dict, traffic: dict):
    from deeplearning4j_tpu.autodiff.fusion import fuse_attention
    from deeplearning4j_tpu.modelimport.tensorflow import (
        TensorflowFrameworkImporter)
    from deeplearning4j_tpu.nn.updaters import Adam
    path = os.path.join(
        BENCH_DIR, cfg["graph_dir"],
        f"frozen_b{traffic['batch']}_s{traffic['seq_len']}.pb.gz")
    with gzip.open(path) as f:
        sd = TensorflowFrameworkImporter.import_graph_def(f.read(),
                                                          trainable=True)
    report = fuse_attention(sd)
    if report.matched != cfg["num_hidden_layers"] or report.unmatched:
        raise ValueError(f"fuse_attention matched {report.matched} sites, "
                         f"left {report.unmatched}: {report.reasons[:2]}")
    pooled = sd._vars[OUTPUT].mean(axis=1)
    logits = pooled.mmul(sd.var("cls_W", weights["cls/W"])) \
        + sd.var("cls_b", weights["cls/b"])
    sd.set_loss(sd.call("loss.softmax_ce_logits", sd.placeholder("labels"),
                        logits))
    upd = cfg["assumed"]["updater"]
    if upd["kind"] != "adam":
        raise ValueError("bert_base is configured for Adam")
    sd.set_updater(Adam(learning_rate=upd["learning_rate"],
                        beta1=upd["beta1"], beta2=upd["beta2"],
                        epsilon=upd["epsilon"]))
    sd.set_dtype({"bfloat16": "BFLOAT16",
                  "float32": "FLOAT"}[cfg["compute_dtype"]])
    used = set()
    for name in sd.variables():
        leaf = reference_name(name)
        if leaf is None:
            continue
        if sd._values[name].shape != weights[leaf].shape:
            raise ValueError(f"{name}: shape {sd._values[name].shape} != "
                             f"{leaf} {weights[leaf].shape}")
        sd.set_value(name, weights[leaf])
        used.add(leaf)
    if used != set(weights):
        raise ValueError("the reference's leaves are not the program's: "
                         f"{sorted(set(weights) - used)[:4]}")
    return sd


def params(sd) -> dict:
    out = {}
    for name in sd.variables():
        leaf = reference_name(name)
        if leaf is not None:
            out[leaf] = sd._values[name]
    return out
