"""The program's spans and kernel names over the device's timeline
(``harness/spans.py`` and the five readers on it), on a trace and a ring made
by hand. Times are nanoseconds; the ring's are on a wall clock that runs
``OFFSET`` ahead of the trace's.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
"""

from types import SimpleNamespace as NS

import pytest

from benchmarks.harness import peaks, spans
from benchmarks.harness import trace as tr
from benchmarks.metrics import (device_idle_pct, epilogue_roofline,
                                epilogue_time_pct, flash_roofline,
                                flash_time_pct, idle_unattributed_pct,
                                input_exposed_pct, step_host_exposed_pct)
from deeplearning4j_tpu.runtime import telemetry

OFFSET = 1_790_000_000_000_000_000
LATE = 2_000            # a call span opens this long after its frame starts

FLASH = ("%jvp_flash_fwd_.3 = (bf16[384,512,64]{2,1,0}, "
         "f32[384,512,128]{2,1,0}) custom-call(bf16[384,512,64]{2,1,0} %q, "
         "bf16[384,512,64]{2,1,0} %k, bf16[384,512,64]{2,1,0} %v), "
         'custom_call_target="tpu_custom_call"')
AFFINE_BWD = ("%transpose_jvp_affine_act_bwd__.7 = (bf16[1000,256]{1,0}, "
              "f32[1,256]{1,0}) custom-call(bf16[1000,256]{1,0} %x), "
              'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.12 = bf16[1000,256]{1,0} fusion(bf16[1000,256]{1,0} %x)"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def profile():
    """A window of 10 ms with two calls of 4 ms. In each call the device
    works from 1.15 ms to 3.6 ms after the frame's start; the rest idles."""
    ops, frames = [], []
    for call in (500_000, 5_000_000):
        frames.append(ev("$graph.py:798 fit_on_device", call, 4_000_000))
        ops += [ev(FLASH, call + 1_150_000, 1_000_000),
                ev(FUSION, call + 2_150_000, 1_050_000),
                ev(AFFINE_BWD, call + 3_200_000, 400_000)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(tr.WINDOW_SPAN, 0, 10_000_000), *frames,
        ev("$array.py:631 _value", 4_100_000, 300_000)])])
    return NS(planes=[dev, host])


def ring_events(stretch=1.0):
    """What the program leaves for the two calls: stage 0.1-1.0 ms, prepare
    to 1.1 ms, the enqueue to 1.2 ms, the readback 3.5-3.9 ms after each
    frame's start. ``stretch`` scales the call spans' durations."""
    out, ids = [], iter(range(1, 100))
    for call in (500_000, 5_000_000):
        root = next(ids)

        def span(name, t0, t1, parent=root, **more):
            return {"t": (OFFSET + t1) / 1e9, "type": "span",
                    "name": "train.phase." + name, "trace": root,
                    "span": next(ids), "parent": parent,
                    "t0_ns": OFFSET + t0, "t1_ns": OFFSET + t1,
                    "duration_s": (t1 - t0) / 1e9, **more}
        out += [span("stage_s", call + 100_000, call + 1_000_000),
                span("prepare_s", call + 1_000_000, call + 1_100_000),
                span("step_s", call + 1_100_000, call + 1_200_000, step=0),
                span("readback_s", call + 3_500_000, call + 3_900_000)]
        took = int((4_000_000 - LATE - 1_000) * stretch)
        root_ev = span("call_s", call + LATE, call + LATE + took, parent=None,
                       entry="ComputationGraph.fit_on_device")
        root_ev["span"] = root
        out.append(root_ev)
    return out


@pytest.fixture
def ring(monkeypatch):
    """An empty flight ring in the program's telemetry; -> fill(events)."""
    flight = telemetry.FlightRecorder()
    monkeypatch.setattr(telemetry, "flight", flight)
    flight.record({"t": (OFFSET - 5_000_000) / 1e9, "type": "compile"})

    def fill(events):
        for e in events:
            flight.record(e)
    return fill


def test_the_offset_is_recovered(ring):
    ring(ring_events())
    reduced = tr.Reduced(profile())
    assert spans.entry_frames(reduced) == [(500_000, 4_500_000),
                                           (5_000_000, 9_000_000)]
    assert spans.align(reduced, telemetry.spans()) == OFFSET + LATE


def test_idle_is_split_over_the_innermost_spans(ring):
    ring(ring_events())
    reduced = tr.Reduced(profile())
    idle = spans.idle_by_span(reduced, telemetry.spans(), OFFSET)
    # per call: the device starts 1.15 ms in (0.9 staged, 0.1 prepared, 0.05
    # of the enqueue) and stops at 3.6 ms (0.3 of the readback's 0.4 ms)
    assert idle["train.phase.stage_s"] == pytest.approx(2 * 900e-6)
    assert idle["train.phase.prepare_s"] == pytest.approx(2 * 100e-6)
    assert idle["train.phase.step_s"] == pytest.approx(2 * 50e-6)
    assert idle["train.phase.readback_s"] == pytest.approx(2 * 300e-6)
    # the rest: before, between and after the calls, and 0.1 ms at each
    # call's head and tail that no child span covers
    assert idle[None] == pytest.approx(10e-3 - 2 * 2.45e-3 - 2 * 1.35e-3)


def test_the_three_exposed_shares_add_up_to_the_idle_share(ring):
    ring(ring_events())
    ctx = {"trace": tr.Reduced(profile())}
    shares = [m.read(ctx)["value"] for m in
              (input_exposed_pct, step_host_exposed_pct,
               idle_unattributed_pct)]
    assert shares[0] == pytest.approx(100 * 2 * 900e-6 / 10e-3, rel=1e-3)
    assert shares[1] == pytest.approx(100 * 2 * 450e-6 / 10e-3, rel=1e-3)
    assert sum(shares) == pytest.approx(
        100 * ctx["trace"].idle_share_fullest())
    assert sum(shares) == pytest.approx(device_idle_pct.read(ctx)["value"])


def test_spans_that_do_not_pair_give_no_metric(ring, capsys):
    ring(ring_events(stretch=1.05))
    ctx = {"trace": tr.Reduced(profile())}
    assert spans.align(ctx["trace"], telemetry.spans()) is None
    assert "do not pair" in capsys.readouterr().err
    for m in (input_exposed_pct, step_host_exposed_pct,
              idle_unattributed_pct):
        assert m.read(ctx) is None


def test_clocks_that_drift_give_no_metric(ring):
    events = ring_events()
    for e in events[5:]:                      # the second call, 0.6 ms late
        e["t0_ns"] += 600_000
        e["t1_ns"] += 600_000
    ring(events)
    assert input_exposed_pct.read({"trace": tr.Reduced(profile())}) is None


def test_a_ring_that_lost_the_windows_start_gives_no_metric(monkeypatch):
    flight = telemetry.FlightRecorder()
    monkeypatch.setattr(telemetry, "flight", flight)
    for e in ring_events():       # nothing older than the window is left
        flight.record(e)
    ctx = {"trace": tr.Reduced(profile())}
    assert idle_unattributed_pct.read(ctx) is None


def test_a_program_without_spans_or_a_run_without_a_trace_gives_no_metric():
    reduced = tr.Reduced(profile())
    parent = NS(flight=telemetry.FlightRecorder())       # no ``spans``
    assert spans.exposed_pct(reduced, parent, None) is None
    for m in (input_exposed_pct, flash_time_pct, epilogue_time_pct):
        assert m.read({"trace": None}) is None


def test_kernel_shares_find_the_named_instructions():
    ctx = {"trace": tr.Reduced(profile())}
    busy = 2 * 2_450_000
    assert flash_time_pct.read(ctx)["value"] == pytest.approx(
        100 * 2 * 1_000_000 / busy)
    assert epilogue_time_pct.read(ctx)["value"] == pytest.approx(
        100 * 2 * 400_000 / busy)
    assert spans.kernel_time_pct(ctx["trace"], ("layer_norm_act_",)) is None
    # a kernel the transforms wrapped another way, and one before its name
    assert spans.kernel_time_pct(ctx["trace"], ("fusion",))["value"] == \
        pytest.approx(100 * 2 * 1_050_000 / busy)
    unnamed = NS(planes=[NS(name="/device:TPU:0", lines=[NS(
        name="XLA Ops", events=[ev(FLASH.replace("jvp_flash_fwd_", "jvp__"),
                                   0, 1000)])])])
    assert flash_time_pct.read({"trace": tr.Reduced(unnamed)}) is None


def test_the_roofline_readers_still_match_the_renamed_instructions():
    assert flash_roofline.classify(FLASH) == ("fwd", (1, 384, 512, 64), 2)
    assert epilogue_roofline.cost(AFFINE_BWD) == \
        epilogue_roofline.flops.affine_act_cost(1000, 256, 2, backward=True)
    assert tr.short_name(AFFINE_BWD) == \
        "transpose_jvp_affine_act_bwd__ tpu_custom_call bf16[1000,256]"
    ctx = {"trace": tr.Reduced(profile()),
           "peaks": peaks.peaks_for("TPU v5 lite")}
    assert flash_roofline.read(ctx)["value"] > 0
    assert epilogue_roofline.read(ctx)["value"] > 0
