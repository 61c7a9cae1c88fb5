"""The latent-attention layers' share of the device's busy time
(``device_trace``): the projections to queries, to the latent and the rotary
key and from the latent up to keys and values, the latent's norm, the rotary
embedding, the blocked score and value products with their softmax passes,
and the output projection, forward and backward, over the busy time of the
busiest device inside the traced window.

The layer is XLA's fusions, which carry no name of the program's, so they
are found by result shapes that only this layer makes, all derived from the
configuration and the mix (``shape_patterns``): a last dimension of the
scored width (``qk_nope_head_dim + qk_rope_head_dim``), of the joint
projection (``kv_lora_rank + qk_rope_head_dim``), of the latent, of the
expanded keys and values (``heads x (nope + v)``) or of the heads' output
(``heads x v``) behind the batch's ``[B, T]``; the rotary channels in their
interleaved and de-interleaved forms; the per-head value-wide arrays; and
what a block of the blocked path makes: the row statistics of a query block
and key blocks of the value width, alone or stacked over the ``B x heads``
rows the path walks. The layer's weight-shaped results are among them (the
weight-gradient products, and with them the master-to-compute cast and the
updater's sweep over the same 26M weights a layer), and so are the
compiler's own copies and slices of arrays of these shapes. The trace's event names carry no scope of the
program's, so nothing is found by name. The query projection's own result
``[B, T, heads x 192]`` is as wide as the dense feed-forward in the published
sizes and is left out wherever another width of the configuration equals
it, as is its weight gradient: the share reads low by that product, never
high. Loops and conditionals are left out: their bodies' operations are
events of their own. A program without the layer (the parent of the PR that
brought it) leaves the metric out."""

import inspect
import re

from benchmarks.harness import trace as _trace

_CONTROL = ("while", "conditional", "call")


def shape_patterns(cfg, traffic):
    """-> compiled pattern of the result shapes only this layer makes, or
    None where the configuration or the program has no such layer."""
    try:
        from deeplearning4j_tpu.nn.layers.decoder import \
            LatentAttentionLayer  # noqa: F401
        from deeplearning4j_tpu.ops import causal_attention
    except ImportError:
        return None
    if "kv_lora_rank" not in cfg:
        return None
    B, T = traffic["batch"], traffic["seq_len"]
    H, d = cfg["num_attention_heads"], cfg["hidden_size"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    block = inspect.signature(causal_attention.causal_attention) \
        .parameters["block"].default
    # widths other layers make: a latent-attention width equal to one of
    # them cannot be told apart by shape and is not claimed
    dep = cfg.get("deployment", {})
    taken = {d, cfg["intermediate_size"], cfg["moe_intermediate_size"],
             cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
             cfg["vocab_size"], dep.get("num_experts_routed"),
             cfg["num_experts_per_tok"]}
    lead = rf"{B},{T},"
    wide = [w for w in (nope + rope, rank + rope, rank) if w not in taken]
    shapes = [rf"\[(\d+,)*{w}\]" for w in wide]
    # behind [B, T], and as the shapes of Wkvb's and Wo's gradients
    if H * (nope + vd) not in taken:
        shapes.append(rf"\[{lead}{H * (nope + vd)}\]|"
                      rf"\[{rank},{H * (nope + vd)}\]")
    if H * vd not in taken:
        shapes.append(rf"\[{lead}{H * vd}\]|\[{H * vd},{d}\]")
    # the rotary channels: [B, T, (H | 1,) rope] and the pairs (rope / 2, 2)
    shapes += [rf"\[{lead}({H},|1,)?{rope}\]",
               rf"\[{lead}({H},)?({rope // 2},)?2(,{rope // 2})?\]"]
    # per-head arrays of the value width, in the orders the path moves
    # them, and the expanded keys and values a head
    shapes += [rf"\[{B},({T},{H}|{H},{T}|{H},1,{T}|{T},{H},1),{vd}\]",
               rf"\[{lead}{H},{nope + vd}\]"]
    if T > block and T % block == 0:
        keys = [n for n in range(block, T + 1, block)]
        alone = "|".join(str(n) for n in keys if n not in taken)
        keys = "|".join(str(n) for n in keys)
        shapes += [rf"f32\[(1,)?{block}(,1)?\]",
                   rf"\[({B * H},)?1,({keys}),{vd}\]",
                   rf"\[({B * H},)?({alone}),{vd}\]"]
    return re.compile("|".join(f"(?:{s})" for s in shapes))


def is_mla(name: str, shapes) -> bool:
    short = _trace.short_name(name).split(" ")
    if len(short) > 1 and short[1] in _CONTROL:
        return False
    # the result's shapes: what stands before the operands
    return bool(shapes.search(name.split(" metadata=")[0].split("(%")[0]))


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
               for s, e, name in dev["ops"] if is_mla(name, shapes))
    if not took:
        return None
    return {"value": 100.0 * took / dev["busy_ns"], "unit": "%"}
