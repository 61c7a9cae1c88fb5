"""The flash-attention kernels' share of their roofline (``device_trace``):
the sum over the kernels' events of the least time the chip could take for
that call, max(operations / peak, bytes / bandwidth) from
``harness.flops.flash_cost``, over the sum of their device durations.

An event is one of these kernels when it is a ``tpu_custom_call`` with a
``[batch x heads, tokens, head size]`` result (the program folds batch and
heads into one axis; a 4-D ``[batch, heads, tokens, head size]`` is read
too). No ``pallas_call`` in the
program passes ``name=`` yet, so the shapes are the only handle: two such
results are the dK/dV kernel, one result with the cotangent among four or
more 4-D operands is the dQ kernel, one result otherwise the forward. A
trace with no such event leaves the metric out."""

import re

from benchmarks.harness import flops

_ARRAY = re.compile(r"(bf16|f16|f32)\[(?:(\d+),)?(\d+),(\d+),(\d+)\]")
_SIZE = {"bf16": 2, "f16": 2, "f32": 4}


def classify(name: str):
    """-> (kind, (b, h, t, d), itemsize) or None."""
    head, _, rest = name.partition(" custom-call(")
    if not rest:
        return None
    results = _ARRAY.findall(head)
    if not results:
        return None
    def dims(found):
        return tuple(int(v) if v else 1 for v in found[1:])

    dtype, shape = results[0][0], dims(results[0])
    full = [r for r in results if dims(r) == shape]
    operands = [o for o in _ARRAY.findall(rest.split("), ")[0])
                if dims(o) == shape]
    if len(full) >= 2:
        kind = "dkv"
    elif len(operands) >= 4:
        kind = "dq"
    else:
        kind = "fwd"
    return kind, shape, _SIZE[dtype]


def cost(name: str):
    got = classify(name)
    if got is None:
        return None
    kind, (b, h, t, d), itemsize = got
    return flops.flash_cost(b, h, t, d, itemsize, kind)


def read(ctx):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    got = flops.kernels_roofline_pct(ctx["trace"], ctx["peaks"], cost)
    return None if got is None else {"value": got, "unit": "%"}
