"""The benchmark's own counts of operations and bytes, from shapes alone.

Model counts give ``train_mfu_pct``: forward + backward = 3 x forward,
recomputation not counted. Kernel counts give the ``*_roofline`` metrics:
the least time a call could take is the larger of operations over the peak
rate and bytes over the peak bandwidth.
"""

from __future__ import annotations


def resnet_forward_flops(cfg: dict) -> float:
    """2 x multiply-accumulates of every convolution and the classifier for
    one image (He et al. Table 1 counts 3.8e9 multiply-adds for the 50-layer
    column at 224 x 224, with the stride on the 3x3; on the first 1x1, as
    here, the first block of stages 2 to 4 does its 3x3 at the smaller size
    already and its 1x1 on a quarter of the positions)."""
    size = cfg["image_size"]
    flops = 0.0

    def conv(hw, c_in, c_out, k):
        return 2.0 * hw * hw * c_in * c_out * k * k

    hw = size // 2                                   # 7x7 / 2
    flops += conv(hw, cfg["channels"], cfg["stem_channels"], 7)
    hw //= 2                                         # 3x3 / 2 max pool
    c_in = cfg["stem_channels"]
    exp = cfg["expansion"]
    for s, (n_blocks, ch) in enumerate(zip(cfg["stage_blocks"],
                                           cfg["stage_channels"])):
        for b in range(n_blocks):
            if s > 0 and b == 0:
                hw //= 2                             # stride on conv a
            flops += conv(hw, c_in, ch, 1)
            flops += conv(hw, ch, ch, 3)
            flops += conv(hw, ch, ch * exp, 1)
            if b == 0:
                flops += conv(hw, c_in, ch * exp, 1)
            c_in = ch * exp
    return flops + 2.0 * c_in * cfg["num_classes"]


def bert_forward_flops(cfg: dict, seq_len: int) -> float:
    """One example of ``seq_len`` tokens through the encoder: ``2 P T`` for
    the weight matrices (``P = 12 L d^2`` at intermediate = 4 d; written out
    below for any intermediate size) plus ``4 L T^2 d`` for the two attention
    products. Embedding look-ups, the pooling head, LayerNorm, softmax and
    GeLU are not counted."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    inter = cfg["intermediate_size"]
    weights = layers * (4 * d * d + 2 * d * inter)
    return 2.0 * weights * seq_len + 4.0 * layers * seq_len * seq_len * d


def forward_flops(cfg: dict, traffic: dict) -> float:
    """Forward operations of one example of this configuration."""
    if cfg["input"]["kind"] == "images":
        return resnet_forward_flops(cfg)
    if cfg["input"]["kind"] == "tokens":
        return bert_forward_flops(cfg, traffic["seq_len"])
    raise ValueError(f"no operation count for {cfg['input']['kind']!r}")


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    return 3.0 * forward_flops(cfg, traffic)


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """-> (least seconds, which bound)."""
    t_f = flops / peaks["flops_bf16"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")


def kernels_roofline_pct(trace, peaks, cost_of):
    """The share of their roofline of the ``tpu_custom_call`` events that
    ``cost_of(event name) -> (operations, bytes) or None`` knows: the sum of
    the least seconds each call could take over the sum of their device
    seconds, in percent; None where the trace holds no such event."""
    least = took = 0.0
    for name, (count, seconds) in trace.op_seconds(
            r'custom_call_target="tpu_custom_call"').items():
        cost = cost_of(name)
        if cost is not None:
            least += count * roofline_seconds(*cost, peaks)[0]
            took += seconds
    return 100.0 * least / took if took else None


def affine_act_cost(rows: int, cols: int, itemsize: int, backward: bool):
    """Per-channel scale and shift with an activation over ``[rows, cols]``.
    Forward reads x and writes y (2 operations an element); backward reads
    the cotangent and x or y, writes dx, and reduces the two per-channel
    sums (about 6 operations an element). The per-channel vectors are
    negligible and left out. -> (operations, bytes)."""
    n = rows * cols
    if backward:
        return 6.0 * n, 3.0 * n * itemsize
    return 2.0 * n, 2.0 * n * itemsize


def flash_cost(b: int, h: int, t: int, d: int, itemsize: int, kind: str):
    """One flash-attention kernel over ``[b, h, t, d]``. Forward (``fwd``):
    ``4 b h t^2 d`` operations (QK^T and PV). Backward: ``8 b h t^2 d`` in
    two kernels of 4 each, ``dq`` (dP and dQ) and ``dkv`` (dV and dK); the
    QK^T and dP that they work out again are not counted. Bytes, with
    ``n = b h t d`` elements: fwd reads q, k, v and writes o (4 n); dq reads
    q, k, v, do and writes dq (5 n); dkv reads q, k, v, do and writes dk, dv
    (6 n); the per-row statistics are left out. -> (operations, bytes)."""
    n = b * h * t * d
    arrays = {"fwd": 4, "dq": 5, "dkv": 6}[kind]
    return 4.0 * b * h * t * t * d, float(arrays * n * itemsize)
