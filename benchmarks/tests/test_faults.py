"""One more test of ``correct``: the rest of a run driven with the timed
path broken underneath (``faults.py``), once for each fault a cell can have,
has to read ``correct`` false; unbroken, the same drive reads true
(``test_harness.py``)."""

import jax
import pytest

from benchmarks.harness import run_cell
from benchmarks.tests import faults, tiny
from benchmarks.tests.test_harness import (BERT_LIMITS, RESNET_LIMITS,
                                           compile_events, drive)  # noqa: F401


def broken(fault):
    return lambda cell, seed, devices: fault(
        run_cell.build(cell, seed, devices))


@pytest.mark.parametrize("fault", [faults.epoch_state_unchanged,
                                   faults.epoch_half_batch])
def test_resident_faults_are_not_correct(fault, compile_events):  # noqa: F811
    cell = tiny.resnet_cell("train.resident", limits=RESNET_LIMITS)
    result = drive(cell, compile_events, build_entry=broken(fault))
    assert not result["correct"], result["compared"]


@pytest.mark.parametrize("fault", [faults.samediff_state_unchanged,
                                   faults.samediff_answer_altered])
def test_samediff_faults_are_not_correct(fault, compile_events):  # noqa: F811
    result = drive(tiny.bert_cell(BERT_LIMITS), compile_events,
                   build_entry=broken(fault))
    assert not result["correct"], result["compared"]


def test_exchange_left_out_is_not_correct(compile_events):  # noqa: F811
    if len(jax.devices()) < 4:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=4")
    limits = {"loss1_gap": 1e-3, "opt_medgap_s1": 0.05,
              "delta_medgap_s2": 0.1}
    cell = tiny.resnet_cell("train.dp4", chips=4, limits=limits)
    sound = drive(cell, compile_events)
    assert sound["correct"], sound["compared"]
    result = drive(cell, compile_events,
                   build_entry=broken(faults.wrapper_no_exchange))
    assert not result["correct"], result["compared"]
