"""The fused epilogue kernels' share of the device's busy time: the device
time of the operations whose instruction name holds ``affine_act_`` or
``layer_norm_act_`` (the program's ``pallas_call(name=...)``, forward and
backward, whatever transform wrapped them) over the busy time of the busiest
device inside the traced window (``device_trace``). The copies and reshapes
XLA puts around the kernels are not in it."""

from benchmarks.harness import spans


def read(ctx):
    return spans.kernel_time_pct(ctx["trace"],
                                 ("affine_act_", "layer_norm_act_"))
