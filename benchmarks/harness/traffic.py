"""The one generator of training traffic.

A traffic mix is a data file under ``benchmarks/traffic/``: the entry point,
batch, number of distinct batches, and what the entry point needs beside
them. The shapes of one example come from the configuration's ``input`` and
``labels``. Every seed gives the same sizes, so a seed changes the values
and never the work.
"""

from __future__ import annotations

import numpy as np


def batches(seed: int, cfg: dict, traffic: dict):
    """-> (features, labels): host arrays of ``batches * batch`` rows that
    all differ, drawn from ``seed``. ``traffic["seq_len"]`` sizes token
    inputs; image sizes are the configuration's."""
    rng = np.random.default_rng(int(seed))
    n = traffic["batch"] * traffic["batches"]
    inp, lab = cfg["input"], cfg["labels"]
    if inp["kind"] == "images":
        s = inp["image_size"]
        x = rng.standard_normal((n, s, s, inp["channels"]), dtype=np.float32)
    elif inp["kind"] == "tokens":
        x = rng.integers(0, inp["vocab_size"], (n, traffic["seq_len"]),
                         dtype=np.int32)
    else:
        raise ValueError(f"unknown input kind {inp['kind']!r}")
    if lab["kind"] != "one_hot":
        raise ValueError(f"unknown label kind {lab['kind']!r}")
    y = np.eye(lab["classes"], dtype=np.float32)[
        rng.integers(0, lab["classes"], n)]
    return x, y


def split(x, y, traffic):
    """The rows as the list of per-step batches the reference follows."""
    b = traffic["batch"]
    return [(x[i * b:(i + 1) * b], y[i * b:(i + 1) * b])
            for i in range(traffic["batches"])]
