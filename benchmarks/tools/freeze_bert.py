#!/usr/bin/env python3
"""Freeze ``transformers.TFBertModel`` at one batch and length, strip every
weight to a one-value splat, and write the small structure-only GraphDef.

    python benchmarks/tools/freeze_bert.py --batch 32 --seq 512 \
        --out benchmarks/configs/bert_base/frozen_b32_s512.pb.gz

Run by hand on the CPU, once per (batch, length): the importer folds TF's
shape arithmetic, so both are baked into the graph. Freezing costs over a
minute and a 438 MB GraphDef; the run reads the 16 KB result and sets every
weight on the device from the seed. ``--config`` overrides keys of
``BertConfig`` (a JSON object) for the tests' tiny graph.
"""

import argparse
import gzip
import json
import os

import numpy as np


def freeze(batch: int, seq: int, overrides: dict):
    os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
    import tensorflow as tf
    from tensorflow.python.framework import tensor_util
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)
    from transformers import BertConfig, TFBertModel

    model = TFBertModel(BertConfig(**overrides))  # bert-base-uncased sizes

    @tf.function
    def f(ids):
        return model(ids).last_hidden_state

    conc = f.get_concrete_function(tf.TensorSpec([batch, seq], tf.int32))
    frozen = convert_variables_to_constants_v2(conc)
    gd = frozen.graph.as_graph_def()
    stripped = 0
    for node in gd.node:
        if node.op != "Const":
            continue
        t = node.attr["value"].tensor
        v = tensor_util.MakeNdarray(t)
        if v.dtype.kind == "f" and v.ndim >= 1 and v.size > 16:
            node.attr["value"].tensor.CopyFrom(tensor_util.make_tensor_proto(
                np.zeros((), v.dtype).item(), dtype=t.dtype, shape=v.shape))
            stripped += 1
    return (gd, frozen.inputs[0].name.split(":")[0],
            frozen.outputs[0].name.split(":")[0], stripped)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--seq", type=int, required=True)
    ap.add_argument("--config", default="{}")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    gd, iname, oname, stripped = freeze(args.batch, args.seq,
                                        json.loads(args.config))
    with gzip.GzipFile(args.out, "wb", mtime=0) as f:
        f.write(gd.SerializeToString())
    print(json.dumps({"out": args.out, "input": iname, "output": oname,
                      "nodes": len(gd.node), "weights_stripped": stripped,
                      "bytes": os.path.getsize(args.out)}))


if __name__ == "__main__":
    main()
