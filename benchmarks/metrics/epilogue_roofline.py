"""The fused epilogue kernels' share of their roofline (``device_trace``):
the sum over the kernel's events of the least time the chip could take for
that call, max(operations / peak, bytes / bandwidth) from
``harness.flops.affine_act_cost``, over the sum of their device durations.

An event is one of these kernels when it is a ``tpu_custom_call`` whose
first result is a 2-D array (``_affine_act`` works on ``[rows, channels]``);
a tuple result (dx and the per-channel sums) is the backward kernel. No
``pallas_call`` in the program passes ``name=`` yet, so the shapes are the
only handle. A trace with no such event (the dispatcher took the reference
path, as on four chips) leaves the metric out."""

import re

from benchmarks.harness import flops

_CALL = re.compile(r'^%[\w.\-]+ = (\()?(bf16|f32|f16)\[(\d+),(\d+)\]')
_SIZE = {"bf16": 2, "f16": 2, "f32": 4}


def cost(name: str):
    m = _CALL.match(name)
    if not m:
        return None
    return flops.affine_act_cost(int(m.group(3)), int(m.group(4)),
                                 _SIZE[m.group(2)], backward=bool(m.group(1)))


def read(ctx):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    got = flops.kernels_roofline_pct(ctx["trace"], ctx["peaks"], cost)
    return None if got is None else {"value": got, "unit": "%"}
