"""The sparse-expert layers' share of the device's busy time
(``device_trace``): routing, the gather of the tokens routed to the experts
held here, the experts' grouped products, the weighted scatter back, forward
and backward, over the busy time of the busiest device inside the traced
window.

The grouped products are the compiler's ``ragged-dot`` kernels and are found
by instruction name. The rest is XLA's fusions, which carry no name of the
program's, so they are found by what only these layers make, from the
configuration: a result with the rows of one chunk of sorted assignments
(``SparseExpertLayer.chunk_rows``), one entry per assignment (tokens x
experts a token), the router's scores ``[tokens, routed experts]`` or the
chosen ``[tokens, experts a token]``, or the float32 ``[tokens, hidden]``
the chunks add into. An event that names one of the program's ``moe.``
scopes counts too. Loops and conditionals are left out: their bodies'
operations are events of their own. A program without the layer (the parent
of the PR that brought it) leaves the metric out."""

import re

from benchmarks.harness import trace as _trace

_CONTROL = ("while", "conditional", "call")


def patterns(cfg, traffic):
    """-> compiled pattern of the result shapes only these layers make, or
    None where the program has no such layer."""
    try:
        from deeplearning4j_tpu.nn.layers.decoder import SparseExpertLayer
    except ImportError:
        return None
    dep = cfg.get("deployment")
    if not dep or "num_experts_routed" not in dep:
        return None
    tokens = traffic["batch"] * traffic["seq_len"]
    k, routed = cfg["num_experts_per_tok"], dep["num_experts_routed"]
    rows = SparseExpertLayer(num_experts=routed, top_k=k,
                             held=tuple(dep["held"])).chunk_rows(tokens)
    shapes = [rf"\[{rows},\d+\]", rf"\[{rows}\]", rf"\[{tokens * k}\]",
              rf"\[{tokens},{routed}\]", rf"\[{tokens},{k}\]",
              rf"f32\[{tokens},{cfg['hidden_size']}\]"]
    return re.compile("|".join(shapes))


def is_moe(name: str, shapes) -> bool:
    short = _trace.short_name(name).split(" ")
    if len(short) > 1 and short[1] in _CONTROL:
        return False
    if "ragged-dot" in short[0] or "moe." in name:
        return True
    # the result's shapes: what stands before the operands
    return bool(shapes.search(name.split(" metadata=")[0].split("(%")[0]))


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    shapes = patterns(ctx["config"], ctx["traffic"])
    if shapes is None:
        return None
    lo, hi = tr.window
    dev = max(tr.devices.values(), key=lambda d: d["busy_ns"])
    took = sum(max(0, min(e, hi) - max(s, lo))
               for s, e, name in dev["ops"] if is_moe(name, shapes))
    if not took:
        return None
    return {"value": 100.0 * took / dev["busy_ns"], "unit": "%"}
