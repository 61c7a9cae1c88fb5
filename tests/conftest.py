"""Test configuration: everything runs on the CPU with eight virtual devices.

Mirrors the reference's "fake cluster" test strategy (SURVEY.md §4: Spark
local[*] + threads-as-GPUs): multi-chip sharding logic is validated on N
virtual CPU devices via ``xla_force_host_platform_device_count``.
"""

import os

# Force the CPU with eight virtual devices, before jax is imported anywhere;
# children that tests spawn inherit both.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture(scope="session")
def smoke():
    """``chip_smoke.py`` as a module: its phases rehearse here at a tiny
    size, and its edge values of a cast serve the staging tests."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses resolves the module
    spec.loader.exec_module(mod)
    return mod


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy tests (imports of real TF/BERT graphs, zoo builds, "
        "multihost, ring-attention grads) — excluded from the fast suite "
        "via -m 'not slow'")


def _coverage_ledgers():
    """This process's coverage ledgers, as their own reports (what
    test_zz_coverage_floor.py holds to its floors)."""
    import deeplearning4j_tpu.ops as ops
    from deeplearning4j_tpu.nn import memory
    from deeplearning4j_tpu.runtime import faults, telemetry
    return {"ops": ops.coverage_report(),
            "policies": memory.policy_coverage_report(),
            "faults": faults.coverage_report(),
            "telemetry": telemetry.coverage_report()}


def _xdist_ledger_dir(config):
    """Where the workers of one xdist run leave their ledgers for the
    floors to merge: the run's base temp directory, the parent of each
    worker's own, which pytest makes anew for every run and clears away
    itself. None in a single process, whose ledgers are whole."""
    if not hasattr(config, "workerinput"):
        return None
    # the factory behind the tmp_path_factory fixture; a hook has no other
    # way to it
    return str(config._tmp_path_factory.getbasetemp().parent)


@pytest.hookimpl(trylast=True)
def pytest_runtest_teardown(item, nextitem):
    """Under xdist the ledgers are per-process and a worker runs only its
    share of the files. When a worker has run the last test of a file it
    writes its ledgers (everything it has marked so far) under that file's
    name; ``coverage_ledgers`` below waits for the files and merges them."""
    path = _xdist_ledger_dir(item.config)
    if path is None:
        return
    if nextitem is not None and nextitem.fspath == item.fspath:
        return
    name = os.path.join(path, item.fspath.basename + ".ledgers")
    with open(name + ".tmp", "w") as f:
        json.dump(_coverage_ledgers(), f)
    os.replace(name + ".tmp", name + ".json")


@pytest.fixture(scope="module")
def coverage_ledgers(request):
    """Every process's coverage ledgers, for the floors to merge: this
    process's own and, under xdist, those of every other collected file,
    waited for until the worker that ran it has written them. ``--dist
    loadfile`` (what tier-1 runs) keeps a file on one worker and hands out
    files in collection order, so nothing is queued behind the floors'
    own file and the wait ends when the slowest other file does."""
    path = _xdist_ledger_dir(request.config)
    if path is None:
        return [_coverage_ledgers()]
    if not any("loadfile" in arg
               for arg in request.config.workerinput["mainargv"]):
        pytest.skip("under xdist a file's ledgers are whole only with "
                    "--dist loadfile")
    wanted = {item.fspath.basename + ".ledgers.json"
              for item in request.session.items} - {
                  request.fspath.basename + ".ledgers.json"}
    deadline = time.monotonic() + 900
    while not wanted <= set(os.listdir(path)):
        if time.monotonic() > deadline:
            raise AssertionError(
                "no ledgers after 900 s from: "
                f"{sorted(wanted - set(os.listdir(path)))}")
        time.sleep(0.5)
    reports = [_coverage_ledgers()]
    for name in sorted(wanted):
        with open(os.path.join(path, name)) as f:
            reports.append(json.load(f))
    return reports
