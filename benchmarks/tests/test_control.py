"""The control of ``correct`` at a size a test run can hold: the plain
reference put in the program's place one precision below the configuration's
(fp8 for bfloat16) has to come out as not correct, under limits that a sound
run (``test_harness.py``, the same limits) passes. The chip readings at the
cells' own sizes are in ``PERF.md`` and ``limits/<cell>.json``."""

import pytest

from benchmarks.tests import tiny
from benchmarks.tests.test_harness import BERT_LIMITS, RESNET_LIMITS
from benchmarks.tools import control


@pytest.mark.parametrize("make, limits", [
    (lambda l: tiny.resnet_cell("train.resident", limits=l), RESNET_LIMITS),
    (tiny.bert_cell, BERT_LIMITS)], ids=["resnet50", "bert_base"])
def test_fp8_control_is_not_correct(make, limits):
    cell = make(limits)
    nums, correct, compared = control.readings(cell, 11, precision="fp8")
    assert not correct, compared
    assert nums["loss1_gap"]["value"] > limits["loss1_gap"]


@pytest.mark.parametrize("make, limits", [
    (lambda l: tiny.resnet_cell("train.resident", limits=l), RESNET_LIMITS),
    (tiny.bert_cell, BERT_LIMITS)], ids=["resnet50", "bert_base"])
def test_half_batch_reference_is_not_correct(make, limits):
    cell = make(limits)
    _, correct, compared = control.readings(cell, 11, fault="half_batch")
    assert not correct, compared
