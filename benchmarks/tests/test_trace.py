"""The trace reduction on a small trace made by hand: objects with the
attributes of ``jax.profiler.ProfileData`` (planes, lines, events with
``start_ns`` and ``duration_ns``). Times in the comments are nanoseconds."""

from types import SimpleNamespace as NS

import pytest

from benchmarks.harness import trace as tr

KERNEL = ('%jvp___.7 = bf16[1000,256]{1,0} custom-call(bf16[1000,256]{1,0} '
          '%x), custom_call_target="tpu_custom_call"')
CONV = "%convolution.3 = bf16[8,56,56,64]{3,2,1,0} convolution(bf16[8] %a)"
AR = "%all-reduce-start.5 = f32[64]{0} all-reduce-start(f32[64]{0} %g)"
WHILE = "%while.4 = (s32[]{:T(128)}, f32[2,2]{1,0}) while(%tuple.1)"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def profile():
    dev0 = NS(name="/device:TPU:0", lines=[
        NS(name="Steps", events=[ev("0", 0, 1000)]),
        NS(name="XLA Ops", events=[
            ev(WHILE, 100, 500),           # 100..600, holds the next two
            ev(CONV, 100, 200),            # 100..300
            ev(KERNEL, 300, 100),          # 300..400
            ev(AR, 550, 250),              # 550..800, 200 of it uncovered
            ev(CONV, 900, 50),             # 900..950
        ])])
    dev1 = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[ev(CONV, 100, 100), ev(AR, 150, 100)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[
            ev(tr.WINDOW_SPAN, 0, 1000),
            ev("upload", 600_000, 0),
            NS(name="astype", start_ns=790, duration_ns=120_000, stats=[]),
        ])])
    return NS(planes=[dev0, dev1, host])


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.total([(0, 3), (5, 7)]) == 5
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_busy_idle_and_window():
    r = tr.Reduced(profile())
    assert r.window == (0, 1000)
    d0 = r.devices["/device:TPU:0"]
    assert d0["busy_ns"] == 500 + 200 + 50          # 100..800 and 900..950
    assert r.idle_share_fullest() == pytest.approx(1 - 750 / 1000)
    assert r.busy_s() == pytest.approx((750 + 150) / 2 / 1e9)
    assert r.window_s == pytest.approx(1e-6)


def test_kernel_sum_and_self_times():
    r = tr.Reduced(profile())
    got = r.op_seconds(r'custom_call_target="tpu_custom_call"')
    assert got == {KERNEL: [1, pytest.approx(100e-9)]}
    top = dict(r.top_ops())
    assert top["convolution convolution bf16[8,56,56,64]"] == \
        pytest.approx(250e-9)
    # the while's own time: 500 less its two children and the collective's
    # first 50
    assert top["while while s32[]"] == pytest.approx(150e-9)
    assert tr.short_name(KERNEL) == "jvp___ tpu_custom_call bf16[1000,256]"


def test_collective_overlap():
    r = tr.Reduced(profile())
    d0 = r.devices["/device:TPU:0"]
    assert d0["collective_ns"] == 250
    # compute (the while included) covers 550..600 of the all-reduce
    assert d0["collective_exposed_ns"] == 200
    d1 = r.devices["/device:TPU:1"]
    assert d1["collective_exposed_ns"] == 50        # 200..250


def test_idle_gaps_are_named_by_the_host():
    r = tr.Reduced(profile())
    gaps = dict(r.idle_gaps())
    # gaps under 20 us are dropped: every gap of this tiny trace is
    assert gaps == {}


def test_a_trace_without_device_operations_is_refused():
    host_only = NS(planes=[NS(name="/host:CPU", lines=[])])
    with pytest.raises(ValueError):
        tr.Reduced(host_only)


def test_kernel_roofline_reader_on_the_trace():
    from benchmarks.harness import peaks
    from benchmarks.metrics import epilogue_roofline, flash_roofline
    ctx = {"trace": tr.Reduced(profile()),
           "peaks": peaks.peaks_for("TPU v5 lite")}
    # forward over [1000, 256] bf16: 2 x 512,000 bytes at 819 GB/s, in 100 ns
    want = 100.0 * (2 * 1000 * 256 * 2 / 819e9) / 100e-9
    assert epilogue_roofline.read(ctx)["value"] == pytest.approx(want)
    assert flash_roofline.read(ctx) is None       # no attention kernel ran
    flash = ("%jvp__.3 = (bf16[384,512,64]{2,1,0}, f32[384,512,128]{2,1,0}) "
             "custom-call(bf16[384,512,64]{2,1,0} %q, bf16[384,512,64]{2,1,0} "
             "%k, bf16[384,512,64]{2,1,0} %v), "
             'custom_call_target="tpu_custom_call"')
    assert flash_roofline.classify(flash) == ("fwd", (1, 384, 512, 64), 2)
    assert flash_roofline.classify(KERNEL) is None
