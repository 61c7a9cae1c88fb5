"""The delta-rule kernels' share of their roofline (``device_trace``): the
sum over their events of the least time the chip could take for that call,
max(operations / peak, bytes / bandwidth) from ``harness.flops_gdn``'s
``delta_rule_cost``, over the sum of their device durations.

An event is one of these kernels when its instruction's name holds
``delta_rule_fwd``, ``delta_rule_states`` or ``delta_rule_bwd`` (the
program's ``pallas_call(name=)``, whatever transform wrapped it). Its
shapes give the rest: the heads, positions and widths from its
``[heads, positions, width]`` operands and results, the chunk from the
``[heads, chunks, dk, dv]`` states where the call has them and else from
``P``'s width (the smallest), and the bytes from every array it reads and
writes. The key and value widths are taken as one, as the configuration
has them. A trace with
no such event leaves the metric out."""

import re

from benchmarks.harness import flops, flops_gdn

_NAME = re.compile(r"^%?([\w.\-]+) = ")
_ARRAY = re.compile(r"(bf16|f16|f32)\[([\d,]+)\]")
_SIZE = {"bf16": 2, "f16": 2, "f32": 4}
KINDS = ("fwd", "states", "bwd")


def _kind(name: str):
    m = _NAME.match(name)
    if m is None:
        return None
    return next((k for k in KINDS if f"delta_rule_{k}" in m.group(1)), None)


def cost(name: str):
    """-> (operations, bytes) of one call, or None for another event."""
    kind = _kind(name)
    if kind is None:
        return None
    head = name.split(", custom_call_target")[0]
    arrays = [(t, tuple(int(v) for v in dims.split(",")))
              for t, dims in _ARRAY.findall(head)]
    nbytes = 0
    for t, shape in arrays:
        n = 1
        for v in shape:
            n *= v
        nbytes += n * _SIZE[t]
    # [heads, positions, width] operands and results; the states [heads,
    # chunks, dk, dv] where the call reads or writes them; the chunks' last
    # A, [groups, heads, chunks] float32, has no position axis
    rows3 = [s for _, s in arrays if len(s) == 3 and s[1] > s[2]]
    states = [s for _, s in arrays if len(s) == 4]
    if not rows3:
        return None
    rows, tokens = rows3[0][0], max(s[1] for s in rows3)
    widths = sorted({s[2] for s in rows3 if s[1] == tokens and s[2] > 1})
    if not widths or (not states and len(widths) < 2):
        return None
    chunk_size = tokens // states[0][1] if states else widths[0]
    return flops_gdn.delta_rule_cost(kind, rows, tokens, chunk_size,
                                     widths[-1], widths[-1], nbytes)


def read(ctx):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    got = flops.kernels_roofline_pct(ctx["trace"], ctx["peaks"], cost)
    return None if got is None else {"value": got, "unit": "%"}
