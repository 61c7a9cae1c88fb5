"""The share of the device's busy time that a looped decoder's heads take
(``device_trace``): for every pass the logits of each block of positions,
their logsumexp, the cotangents of both, the products that carry them back
to the hidden states and to the head's weight, and the updater's sweep over
that weight, over the busy time of the busiest device inside the traced
window.

The head is XLA's fusions, which carry no name of the program's (the
trace's event names hold no scope), so they are found by result shapes that
only the head makes, all derived from the configuration, the mix and the
head's block (``shape_patterns``): a last dimension of the vocabulary
(logits and their cotangents ``[block, vocab]``; the weight-shaped ``[hidden,
vocab]`` of the weight gradient, of the master-to-compute cast and of the
updater's sweep, which lie outside the head's scope); and, where the head's
block of positions is no other width of the configuration, what a block
makes without the vocabulary in its shape: the hidden states of one block
and their cotangent ``[block, hidden]``, the blocks stacked over the passes
``[passes x batch x seq / block, block, ...]`` and a block's row statistics
``f32[block]``. Where the block equals another width (2,048 positions beside
a hidden size of 2,048) those are left out: the share then reads low by the
product back to the hidden states and the row reductions, never high. The
exit gate's ``[passes, batch, seq]`` arrays are a thousandth of the head's
work and are not claimed. Loops and conditionals are left out: their bodies'
operations are events of their own. A program without the head (the parent
of the PR that brought it) and a configuration that walks its layers once
leave the metric out."""

import re

from benchmarks.metrics.mla_time_pct import is_mla as _caught


def head_block(seq_len: int):
    """Positions of one pass the program's head scores at a time, or None
    where the program has no such head."""
    try:
        from deeplearning4j_tpu.nn.layers.decoder import LM_HEAD_BLOCK
    except ImportError:
        return None
    return LM_HEAD_BLOCK if seq_len % LM_HEAD_BLOCK == 0 else seq_len


def shape_patterns(cfg, traffic):
    """-> compiled pattern of the result shapes only the head makes, or None
    where the configuration or the program has no such head."""
    if "total_ut_steps" not in cfg:
        return None
    B, T = traffic["batch"], traffic["seq_len"]
    C = head_block(T)
    if C is None:
        return None
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    hd = cfg["head_dim"]
    # widths other layers make: an array of the head's that is shaped by
    # one of them cannot be told apart and is not claimed
    taken = {d, cfg["intermediate_size"], hd,
             cfg["num_attention_heads"] * hd,
             cfg["num_key_value_heads"] * hd, T, B * T}
    if V in taken:
        return None
    shapes = [rf"\[(\d+,)*{V}\]"]
    if C not in taken:
        blocks = cfg["total_ut_steps"] * B * T // C
        shapes += [rf"\[({blocks},|1,)?{C},{d}\]", rf"\[({blocks},|1,)?{C}\]"]
    return re.compile("|".join(f"(?:{s})" for s in shapes))


# one test of an event's result shapes for both shape-found layers
is_head = _caught


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    shapes = shape_patterns(ctx["config"], ctx["traffic"])
    if shapes is None:
        return None
    lo, hi = tr.window
    dev = max(tr.devices.values(), key=lambda d: d["busy_ns"])
    took = sum(max(0, min(e, hi) - max(s, lo))
               for s, e, name in dev["ops"] if is_head(name, shapes))
    if not took:
        return None
    return {"value": 100.0 * took / dev["busy_ns"], "unit": "%"}
