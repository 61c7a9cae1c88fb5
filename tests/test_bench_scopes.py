"""The join of a device trace with the program's scope tables
(``benchmarks/harness/scopes.py``) and the six readers over it, on traces
made by hand in the style of ``benchmarks/tests/test_trace.py``: objects
with the attributes of ``jax.profiler.ProfileData``. Times are nanoseconds.
No device number is read here."""

import importlib
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmarks.harness import scopes
from benchmarks.harness import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("fwd_time_pct", "bwd_time_pct", "recompute_time_pct",
           "updater_time_pct", "scope_unattributed_pct", "attn_time_pct")

WHILE = "%while.4 = (s32[]{:T(128)}, f32[2,2]{1,0}) while(%tuple.1)"
FWD = ("%fusion.1 = bf16[2,16,8]{2,1,0:T(8,128)(2,1)} fusion(%p0), "
       "kind=kLoop, calls=%fused_computation.1")
ATTN = '%causal_flash_fwd.3 = bf16[4,16,8]{2,1,0} custom-call(%q), ' \
       'custom_call_target="tpu_custom_call"'
REMAT = "%fusion.5 = bf16[2,16,8]{2,1,0} fusion(%p0), kind=kLoop"
WGRAD = ("%multiply_reduce_fusion.7 = (f32[]{:T(128)}, f32[8,8]{1,0}) "
         "fusion(%a, %b), kind=kOutput")
ADAM = "%fusion.9 = f32[8,8]{1,0} fusion(%w, %m, %v), kind=kLoop"
COPY = "%copy.11 = f32[8,8]{0,1} copy(%w)"
STRAY = "%fusion.99 = f32[3]{0} fusion(%z), kind=kLoop"


def ins(shape, scope, phase, inside=(), vertex=None, scopes=()):
    return {"shape": shape, "scope": scope, "scopes": list(scopes),
            "phase": phase, "phases_inside": list(inside), "vertex": vertex}


def step_table():
    return {"site": "train.epoch_fn", "module": "jit_epoch_fn",
            "labels": {"vertices": ["l0.attn", "l0.mlp"]},
            "instructions": {
                "while.4": ins("s32[]", "while", "other"),
                "fusion.1": ins("bf16[2,16,8]",
                                "while/body/jvp(forward)/l0.mlp/dot_general",
                                "forward", ["forward"], "l0.mlp",
                                ["forward", "l0.mlp"]),
                "causal_flash_fwd.3": ins(
                    "bf16[4,16,8]",
                    "while/body/jvp(forward)/l0.attn/attn.full/pallas_call",
                    "forward", [], "l0.attn",
                    ["forward", "l0.attn", "attn.full"]),
                "fusion.5": ins(
                    "bf16[2,16,8]",
                    "while/body/transpose(jvp(forward))/checkpoint/"
                    "rematted_computation/l0.attn/attn.full/mul",
                    "recompute", ["recompute"], "l0.attn",
                    ["forward", "l0.attn", "attn.full"]),
                "multiply_reduce_fusion.7": ins(
                    "f32[]", "while/body/sentinel/reduce_sum", "sentinel",
                    ["backward", "sentinel"], None, ["sentinel"]),
                "fusion.9": ins("f32[8,8]",
                                "while/body/updater/cond/branch_1_fun/add",
                                "updater", ["updater"], None, ["updater"]),
                "copy.11": ins("f32[8,8]", "", "other"),
            }}


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def reduced(events, window=(0, 1000)):
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=events)])
    idle = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[ev(FWD, 0, 10)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(tr.WINDOW_SPAN, window[0], window[1] - window[0])])])
    return tr.Reduced(NS(planes=[dev, idle, host]))


def step_trace():
    return reduced([
        ev(WHILE, 100, 800),          # 100..900, holds all but the stray
        ev(FWD, 100, 200),            # forward 200
        ev(ATTN, 300, 100),           # forward 100, attn.full
        ev(REMAT, 400, 50),           # recompute 50, attn.full
        ev(WGRAD, 450, 250),          # sentinel 250, two phases inside
        ev(ADAM, 700, 100),           # updater 100
        ev(COPY, 800, 60),            # other 60; the while keeps 40 itself
        ev(STRAY, 950, 30),           # in no table: 30
    ])


def test_the_shares_add_up_to_the_busy_time():
    att = scopes.attribute(step_trace(), [step_table()])
    assert att["busy"] == 800 + 30
    assert att["phase"] == {"forward": 300, "recompute": 50,
                            "sentinel": 250, "updater": 100,
                            "other": 60 + 40 + 30}
    assert sum(att["phase"].values()) == att["busy"]
    assert att["joined"] == 800 and att["unjoined"] == 30
    assert att["name_only"] == 0


def test_a_loops_own_time_is_not_counted_twice():
    att = scopes.attribute(step_trace(), [step_table()])
    # the while's 800 hold 760 of its body's events: 40 are its own
    assert att["unattributed_ops"]["while while s32[]"] == 40
    assert "body" not in att["scope"]            # JAX's, not the program's
    assert att["scope"]["forward"] == 350        # recomputed work is under it


def test_the_window_clips_the_events():
    r = reduced([ev(FWD, 0, 400), ev(ADAM, 400, 400)], window=(200, 600))
    att = scopes.attribute(r, [step_table()])
    assert att["busy"] == 400
    assert att["phase"] == {"forward": 200, "updater": 200}


def test_scopes_vertices_and_mixed_fusions():
    att = scopes.attribute(step_trace(), [step_table()])
    assert att["scope"]["attn.full"] == 150
    assert att["scope"]["forward"] == 350        # jvp(...) and transpose(...)
    assert att["vertex"] == {"l0.mlp": {"forward": 200},
                             "l0.attn": {"forward": 100, "recompute": 50}}
    assert att["mixed"] == 250
    assert "dot_general" not in att["scope"]     # the primitive is no scope


def test_a_name_two_programs_hold_is_settled_by_shape():
    other = {"site": "samediff.fit_prepare", "module": "jit_prepare",
             "labels": {}, "instructions": {
                 "fusion.1": ins("f32[8]", "broadcast_in_dim", "other"),
                 "fusion.9": ins("f32[8,8]", "forward/zeros", "forward")}}
    att = scopes.attribute(step_trace(), [other, step_table()])
    # fusion.1 has the step's shape; fusion.9 has both programs' shape and
    # goes to the program that matches more of the window alone
    assert att["phase"]["forward"] == 300 and att["phase"]["updater"] == 100
    assert att["joined"] == 800
    swapped = scopes.attribute(step_trace(), [step_table(), other])
    assert swapped["phase"] == att["phase"]


def test_a_matching_name_of_another_shape_is_not_joined():
    table = step_table()
    table["instructions"]["fusion.1"]["shape"] = "bf16[2,16,9]"
    att = scopes.attribute(step_trace(), [table])
    assert att["name_only"] == 200 and att["joined"] == 600
    assert att["phase"]["forward"] == 100
    assert att["phase"]["other"] == 130 + 200


def test_an_unknown_name_lands_in_the_unattributed_share():
    ctx = {"trace": step_trace(), "scopes": scopes.attribute(
        step_trace(), [step_table()])}
    got = importlib.import_module(
        "benchmarks.metrics.scope_unattributed_pct").read(ctx)
    assert got == {"value": pytest.approx(100 * 130 / 830), "unit": "%"}
    assert ctx["scopes"]["unattributed_ops"]["fusion fusion f32[3]"] == 30


def test_the_readers_read_the_phases_and_the_attention_scopes():
    ctx = {"trace": step_trace(), "scopes": scopes.attribute(
        step_trace(), [step_table()])}
    read = {n: importlib.import_module(f"benchmarks.metrics.{n}").read(ctx)
            for n in READERS}
    want = {"fwd_time_pct": 300, "recompute_time_pct": 50,
            "updater_time_pct": 100, "bwd_time_pct": 0,
            "scope_unattributed_pct": 130, "attn_time_pct": 150}
    for n, ns in want.items():
        assert read[n] == {"value": pytest.approx(100 * ns / 830),
                           "unit": "%"}, n
    phases = sum(read[n]["value"] for n in READERS if n != "attn_time_pct")
    assert phases + 100 * 250 / 830 == pytest.approx(100)     # + sentinel


def test_attention_is_left_out_where_no_scope_names_it():
    table = step_table()
    for i in table["instructions"].values():
        i["scope"] = i["scope"].replace("attn.full/", "")
        i["scopes"] = [c for c in i["scopes"] if c != "attn.full"]
    ctx = {"trace": step_trace(),
           "scopes": scopes.attribute(step_trace(), [table])}
    assert importlib.import_module(
        "benchmarks.metrics.attn_time_pct").read(ctx) is None


@pytest.mark.parametrize("reader", READERS)
def test_a_program_without_tables_leaves_the_metric_out(reader, monkeypatch):
    """The parent of the PR that brought the tables has no
    ``program_scopes``; a program that dispatched nothing has no table."""
    read = importlib.import_module(f"benchmarks.metrics.{reader}").read
    from deeplearning4j_tpu.runtime import telemetry
    telemetry.reset_programs()
    assert read({"trace": step_trace()}) is None          # no table kept
    assert read({"trace": None}) is None                  # no trace
    monkeypatch.delattr(telemetry, "program_scopes")
    assert read({"trace": step_trace()}) is None          # the parent


def test_tables_of_a_program_without_the_registry():
    assert scopes.tables_of(NS()) is None
    assert scopes.tables_of(NS(program_scopes=lambda: [])) is None
    assert scopes.attribute(step_trace(), None) is None


def test_shares_joins_once_and_says_what_it_found(monkeypatch, capsys):
    from deeplearning4j_tpu.runtime import telemetry
    monkeypatch.setattr(telemetry, "program_scopes",
                        lambda site=None: [step_table()])
    ctx = {"trace": step_trace()}
    att = scopes.shares(ctx)
    assert scopes.shares(ctx) is att
    said = capsys.readouterr().err
    assert said.count("phases % of busy") == 1
    assert "forward 36.14" in said and "joined by name and shape 96.39" in said
    assert "scopes: forward 42.17, sentinel 30.12, attn.full 18.07" in said
    assert "l*.mlp 24.10 (for 24.10)" in said
    assert "l*.attn 18.07 (for 12.05 rec 6.02)" in said
    assert "in fusions of more than one phase 30.12" in said
    assert "unattributed: copy copy f32[8,8] 7.23" in said


def test_the_benchmark_declares_the_six():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    decoders = [c for c in cells if c.endswith(".pretrain.s8k")]
    declared = {m["name"]: m for m in bench["per_layer"]}
    names = list(declared)
    first = names.index("fwd_time_pct")
    assert names[first:first + 6] == list(
        ("fwd_time_pct", "bwd_time_pct", "recompute_time_pct",
         "updater_time_pct", "scope_unattributed_pct", "attn_time_pct"))
    # the selected-key cell's attention runs under scopes of its own
    # (``attn.sparse``, ``attn.index``: its own two readers), which are not
    # among ``scopes.ATTENTION``
    dense = [c for c in decoders if not c.startswith("keye_vl2")]
    for name in READERS:
        m = declared[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "%", "lower", "device_trace", "train_examples_per_s")
        assert m["workloads"] == (
            dense if name == "attn_time_pct"
            else decoders if name == "recompute_time_pct" else cells)
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", name + ".py"))
    assert declared["scope_unattributed_pct"]["layer"] == "device"
    assert declared["attn_time_pct"]["layer"] == "kernels"
    assert declared["fwd_time_pct"]["layer"] == "model step"
