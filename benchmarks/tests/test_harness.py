"""The harness's stages (build, first steps, warm-up, window, reference,
comparison, result line) driven through function arguments at a tiny size on
the CPU. The result names the device ``cpu`` and carries no device metric;
the command the driver runs has no such mode and fails without a TPU.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``
(``test_faults.py``'s four-device case wants
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` as well).
"""

import json
import os
import subprocess
import sys
import time

import jax
import pytest

from benchmarks.harness import events, run_cell
from benchmarks.harness.cell import ROOT, Cell, metric_reader
from benchmarks.tests import tiny

RESNET_LIMITS = {"loss1_gap": 1e-3, "opt_medgap_s2": 0.1,
                 "delta_medgap_s2": 0.1}
BERT_LIMITS = {"loss1_gap": 1e-4, "loss_gap": 1e-4, "delta_medgap_s3": 1e-3}


@pytest.fixture(scope="module")
def compile_events():
    return events.CompileEvents()


def drive(cell, compile_events, build_entry=None, seed=2 ** 31 + 11):
    import deeplearning4j_tpu  # noqa: F401
    return run_cell.run(cell, seed, 0.3, False, jax.devices()[:cell.chips],
                        time.perf_counter(), compile_events,
                        build_entry=build_entry)


def check_line(result):
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": result["device"]["count"]}
    assert result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compiled_in_window"] == 0
    for c in result["compared"].values():
        assert set(c) >= {"value", "limit"}
    assert any(c["limit"] is not None for c in result["compared"].values())
    json.dumps(result)


@pytest.mark.parametrize("traffic", ["train.resident", "train.stream"])
def test_resnet_cell_stages(traffic, compile_events):
    cell = tiny.resnet_cell(traffic, limits=RESNET_LIMITS)
    result = drive(cell, compile_events)
    check_line(result)
    assert result["correct"], result["compared"]


def test_bert_cell_stages(compile_events):
    result = drive(tiny.bert_cell(BERT_LIMITS), compile_events)
    check_line(result)
    assert result["correct"], result["compared"]


def test_a_limit_without_its_number_is_not_correct(compile_events):
    cell = tiny.bert_cell(dict(BERT_LIMITS, opt_medgap_s1=1.0))
    result = drive(cell, compile_events)
    assert not result["correct"]          # SameDiff.fit shows no state
    assert result["compared"]["opt_medgap_s1"]["value"] is None


def test_every_cell_finds_its_files_and_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = Cell.load(w["name"])
        assert cell.reference() and cell.program() and cell.entry()
        assert "setup_s" in cell.metric_names("end_to_end")
        for name in cell.metric_names("per_layer"):
            assert callable(metric_reader(name))
        assert cell.limits, w["name"]


def test_readers_return_nothing_off_the_chip():
    ctx = {"peaks": None, "trace": None, "memory": (None, None),
           "data_wait_s": 0.0, "window": {"seconds": 1.0, "examples": 1}}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        for m in json.load(f)["per_layer"]:
            assert metric_reader(m["name"])(ctx) is None, m["name"]


def test_the_command_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))[
        "workloads"][0]["name"]
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", name, "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert got.returncode != 0
    assert "no TPU" in got.stderr and got.stdout.strip() == ""
