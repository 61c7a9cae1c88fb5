"""The flash-attention kernels' share of the device's busy time: the device
time of the operations whose instruction name holds ``flash_`` (the
program's ``pallas_call(name="flash_fwd" | "flash_bwd_dq" | "flash_bwd_dkv"
| "flash_decode_mq")``, whatever transform wrapped them) over the busy time
of the busiest device inside the traced window (``device_trace``). Unlike
``flash_roofline`` it still reads when the kernels get fast, and goes absent
only when none ran."""

from benchmarks.harness import spans


def read(ctx):
    return spans.kernel_time_pct(ctx["trace"], ("flash_",))
