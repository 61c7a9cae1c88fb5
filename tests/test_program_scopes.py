"""The scope registry (ISSUE 39): each training entry point hands the
executable of the program it dispatches to ``telemetry.record_program`` once,
and ``telemetry.program_scopes()`` turns the optimized HLO into a table of
every instruction with its scope path, phase and vertex, which a device
trace is joined with by instruction name (``benchmarks/harness/scopes.py``,
``tests/test_bench_scopes.py``). Tiny sizes, the CPU: what is held here is
the names, the rule, the bound and that nothing compiles twice or stays
alive; every share of a device's time comes from a chip run."""

import contextlib
import gc
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_attention_kept_cells as cells
from deeplearning4j_tpu.autodiff.samediff import SameDiff
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.runtime import telemetry as tel

COMPILED = "/jax/core/compile/backend_compile_duration"


def _fit_decoder(name):
    net, ids = cells._cell(name)
    x = np.concatenate([ids, ids])
    net.fit_on_device(x, np.ones((x.shape[0], 1), np.float32), epochs=1,
                      batch_size=2)
    return net


@pytest.fixture(scope="module", params=["laguna_xs2", "ouro_2_6b"])
def decoder(request):
    """(the cell's name, its graph after one ``fit_on_device`` call, the
    tables the call left): ``laguna`` recomputes six vertices at a time,
    ``ouro`` walks its layers inside a repeated run."""
    tel.reset_programs()
    net = _fit_decoder(request.param)
    return request.param, net, tel.program_scopes()


def _mln():
    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater(Adam(learning_rate=1e-2))
            .input_type(InputType.feed_forward(12))
            .list(DenseLayer(n_out=16, activation="tanh", name="hidden"),
                  DenseLayer(n_out=16, activation="relu"),
                  OutputLayer(n_out=4))
            .build())
    return MultiLayerNetwork(conf).init()


def _mln_data(rng):
    x = rng.standard_normal((8, 12)).astype(np.float32)
    return x, np.eye(4, dtype=np.float32)[rng.integers(0, 4, 8)]


def _samediff():
    sd = SameDiff.create()
    x, y = sd.placeholder("x"), sd.placeholder("y")
    w = sd.var("w", np.full((6, 2), 0.1, np.float32))
    b = sd.var("b", np.zeros((2,), np.float32))
    sd.set_loss(sd.call("loss.softmax_ce_logits", y, x.mmul(w) + b))
    sd.set_updater(Adam(learning_rate=1e-2))
    return sd


def _phases(table):
    return {i["phase"] for i in table["instructions"].values()}


def _scopes(table):
    return {c for i in table["instructions"].values() for c in i["scopes"]}


# ------------------------------------------------------------- the tables

def test_fit_on_device_registers_one_table_a_program(decoder):
    name, net, tables = decoder
    assert [t["site"] for t in tables] == ["train.epoch_fn"]
    t = tables[0]
    assert t["module"] == "jit_epoch_fn"
    assert t["labels"] == {"vertices": list(net._topo)}
    json.dumps(tables)                       # plain data


def test_a_second_call_registers_nothing_more(decoder):
    name, net, _ = decoder
    before = len(tel.program_scopes())
    ids = np.zeros((4, cells.T), np.int32)
    net.fit_on_device(ids, np.ones((4, 1), np.float32), epochs=2,
                      batch_size=2)
    assert len(tel.program_scopes()) == before


def test_the_decoders_phases(decoder):
    """Forward, the recomputed segments, the backward pass and the updater
    are all there, and the sentinel's sums."""
    _, _, tables = decoder
    assert _phases(tables[0]) >= {"forward", "recompute", "backward",
                                  "updater", "sentinel"}


def test_every_vertex_and_an_attention_scope_are_named(decoder):
    name, net, tables = decoder
    named = {i["vertex"] for i in tables[0]["instructions"].values()}
    # vertices that compute nothing of their own (a residual sum fused into
    # its neighbour) may own no instruction; every layer with weights does
    assert named - {None} <= set(net._topo)
    assert {v for v in net._topo if net.params.get(v)} <= named
    scopes = _scopes(tables[0])
    assert "attn.full" in scopes
    assert ("loop.pass" in scopes) == (name == "ouro_2_6b")
    assert ("attn.window" in scopes) == (name == "laguna_xs2")
    # the program's own names alone: no transformation, none of JAX's
    # structure, no primitive
    assert {"forward", "updater", "sentinel"} <= scopes
    assert not scopes & {"while", "body", "checkpoint", "closed_call",
                         "rematted_computation", "dot_general", "jvp", ""}
    assert not any("(" in c for c in scopes)


def test_a_vertex_keeps_its_name_backward_and_recomputed(decoder):
    _, net, tables = decoder
    attn = next(v for v in net._topo if v.endswith(".attn"))
    by = {i["phase"] for i in tables[0]["instructions"].values()
          if i["vertex"] == attn}
    assert by >= {"forward", "recompute", "backward"}
    path = next(i["scope"] for i in tables[0]["instructions"].values()
                if i["vertex"] == attn and i["phase"] == "forward"
                and "attn.full" in i["scope"])
    assert re.search(rf"jvp\(forward\)/(.*/)?{re.escape(attn)}/attn\.full/",
                     path), path


def test_phases_inside_are_phases(decoder):
    _, _, tables = decoder
    for ins in tables[0]["instructions"].values():
        assert set(ins["phases_inside"]) <= set(tel.PHASES) - {"other"}
        assert ins["phase"] in tel.PHASES


def test_multilayer_fit_registers_its_step(rng):
    tel.reset_programs()
    net = _mln()
    net.fit(*_mln_data(rng))
    tables = tel.program_scopes()
    assert [t["site"] for t in tables] == ["train.step"]
    assert tables == tel.program_scopes("train.step")
    assert tel.program_scopes("train.epoch_fn") == []
    t = tables[0]
    assert t["labels"] == {"vertices": ["hidden", "layer1", "layer2"]}
    assert _phases(t) >= {"forward", "backward", "updater"}
    assert "recompute" not in _phases(t)
    assert {i["vertex"] for i in t["instructions"].values()} >= {
        "hidden", "layer1", "layer2"}
    json.dumps(tables)


def test_multilayer_fit_on_device_registers_its_epoch(rng):
    tel.reset_programs()
    net = _mln()
    x, y = _mln_data(rng)
    net.fit_on_device(x, y, epochs=2, batch_size=4)
    tables = tel.program_scopes()
    assert [t["site"] for t in tables] == ["train.epoch_fn"]
    assert tables[0]["module"] == "jit_epoch_fn"
    assert _phases(tables[0]) >= {"forward", "backward", "updater"}


def test_samediff_fit_registers_its_two_programs(rng):
    tel.reset_programs()
    sd = _samediff()
    feeds = [{"x": rng.standard_normal((4, 6)).astype(np.float32),
              "y": rng.standard_normal((4, 2)).astype(np.float32)}
             for _ in range(2)]
    sd.fit(feeds)
    sd.fit(feeds)                            # a warm call registers nothing
    tables = tel.program_scopes()
    assert sorted(t["site"] for t in tables) == ["samediff.fit_prepare",
                                                 "samediff.fit_step"]
    step, = tel.program_scopes("samediff.fit_step")
    assert _phases(step) >= {"forward", "backward", "updater"}
    json.dumps(tables)


def test_parallel_wrapper_registers_its_step(rng):
    from jax.sharding import Mesh
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    tel.reset_programs()
    net = _mln()
    pw = ParallelWrapper(net, mesh=Mesh(np.array(jax.devices()[:2]),
                                        ("data",)))
    pw.fit(DataSet(*_mln_data(rng)))
    tables = tel.program_scopes()
    assert [t["site"] for t in tables] == ["parallel.step"]
    assert _phases(tables[0]) >= {"forward", "backward", "updater"}


# ------------------------------------------------- metadata and nothing else

def _loss_grad_text(net, ids, debug_info=False):
    loss_fn = net._build_loss_fn()
    y = jnp.ones((ids.shape[0], 1), jnp.float32)
    return jax.jit(jax.grad(lambda p: loss_fn(
        p, net.state, None, (jnp.asarray(ids),), (y,), (None,),
        (None,))[0])).lower(net.params).as_text(debug_info=debug_info)


@pytest.mark.parametrize("name", ["laguna_xs2", "ouro_2_6b"])
def test_the_vertex_scopes_are_metadata_only(monkeypatch, name):
    """The lowered text, locations stripped, is the parent's: the same
    graph with the vertex scopes patched out. With locations it names the
    vertices."""
    net, ids = cells._cell(name)
    with_scopes = _loss_grad_text(net, ids)
    located = _loss_grad_text(net, ids, debug_info=True)
    attn = next(v for v in net._topo if v.endswith(".attn"))
    scoped = f"{attn}/attn.full"
    assert scoped in located and scoped not in with_scopes
    real, vertices = jax.named_scope, set(net._topo)
    monkeypatch.setattr(
        jax, "named_scope",
        lambda n: contextlib.nullcontext() if n in vertices else real(n))
    parent = _loss_grad_text(net, ids)
    assert scoped not in _loss_grad_text(net, ids, debug_info=True)
    assert parent == with_scopes


def test_the_layer_scopes_are_metadata_only(monkeypatch, rng):
    x, y = _mln_data(rng)

    def text(debug_info=False):
        net = _mln()
        loss_fn = net._build_loss_fn()
        return jax.jit(jax.grad(lambda p: loss_fn(
            p, net.state, None, x, y, None, None)[0])).lower(
                net.params).as_text(debug_info=debug_info)

    with_scopes = text()
    assert "hidden" in text(debug_info=True)
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda n: contextlib.nullcontext()
        if n in ("hidden", "layer1", "layer2") else real(n))
    assert "hidden" not in text(debug_info=True)
    assert text() == with_scopes


# ------------------------------------------------------ no second compile

def _compiles_of(run):
    n = [0]

    def on(event, secs, **_):
        if event == COMPILED:
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        run()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    return n[0]


@pytest.mark.parametrize("entry", ["fit", "fit_on_device", "samediff"])
def test_the_registry_compiles_nothing_more(monkeypatch, rng, entry):
    """The backend compiles of an entry point's first call, counted with
    the registry and with it patched out: the executable handed over comes
    out of JAX's lowering cache, and the call that follows hits it."""
    x, y = _mln_data(rng)
    feeds = [{"x": x[:, :6], "y": y[:, :2]}]

    def run():
        if entry == "samediff":
            _samediff().fit(feeds)
        elif entry == "fit":
            _mln().fit(x, y)
        else:
            _mln().fit_on_device(x, y, epochs=1, batch_size=4)

    run()                                    # eager primitives, init()
    tel.reset_programs()
    with_registry = _compiles_of(run)
    assert tel.program_scopes()
    monkeypatch.setattr(tel, "record_dispatch", lambda *a, **k: None)
    tel.reset_programs()
    assert _compiles_of(run) == with_registry
    assert tel.program_scopes() == []


@pytest.mark.parametrize("entry", ["fit", "fit_on_device", "parallel"])
def test_the_handle_is_taken_between_the_phase_spans(monkeypatch, rng, entry):
    """After the call, under the call's root span and in neither
    ``prepare_s`` nor ``step_s``: the first call's compile stays in
    ``step_s``, where an operator looks for it."""
    seen = []
    real = tel.record_dispatch
    monkeypatch.setattr(
        tel, "record_dispatch",
        lambda site, *a, **k: (seen.append((site, tel.current_span().name)),
                               real(site, *a, **k)))
    tel.reset_programs()
    net = _mln()
    x, y = _mln_data(rng)
    if entry == "fit":
        net.fit(x, y)
    elif entry == "fit_on_device":
        net.fit_on_device(x, y, epochs=1, batch_size=4)
    else:
        from jax.sharding import Mesh
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
        ParallelWrapper(net, mesh=Mesh(np.array(jax.devices()[:2]),
                                       ("data",))).fit(DataSet(x, y))
    assert seen and {span for _, span in seen} == {"train.phase.call_s"}
    assert len(tel.program_scopes()) == 1


def test_a_plain_function_records_nothing():
    tel.reset_programs()
    tel.record_dispatch("train.epoch_fn", lambda *a: a, (1, 2))
    assert tel.program_scopes() == []


def test_a_second_batch_shape_is_a_second_program(rng):
    """A tail batch of another size retraces the one step function: its
    executable is another program with instruction names of its own."""
    tel.reset_programs()
    net = _mln()
    x, y = _mln_data(rng)
    for rows in (8, 8, 6, 8, 6):
        net.fit(x[:rows], y[:rows])
    tables = tel.program_scopes()
    assert [t["site"] for t in tables] == ["train.step"] * 2
    shapes = [{i["shape"] for i in t["instructions"].values()}
              for t in tables]
    assert any(s.startswith("f32[8,") for s in shapes[0])
    assert any(s.startswith("f32[6,") for s in shapes[1])
    assert not any(s.startswith("f32[6,") for s in shapes[0])


def test_an_ahead_of_time_executable_is_recorded_once():
    tel.reset_programs()
    with jax.named_scope("forward"):
        compiled = jax.jit(lambda a: jnp.tanh(a) * 2).lower(
            jnp.ones((3,))).compile()
    for _ in range(2):
        compiled(jnp.ones((3,)))
        tel.record_dispatch("train.step", compiled, (jnp.ones((3,)),))
    table, = tel.program_scopes()
    assert table["site"] == "train.step" and table["instructions"]


# ----------------------------------------------------- bounded, and released

class _Module:
    def __init__(self, name, text):
        self.name, self.text, self.rendered = name, text, 0

    def to_string(self):
        self.rendered += 1
        return self.text


class _Executable:
    def __init__(self, *modules):
        self.modules = modules

    def hlo_modules(self):
        return list(self.modules)


def test_the_registry_keeps_the_newest_eight():
    tel.reset_programs()
    mods = [_Module(f"jit_p{i}", HAND_MADE) for i in range(11)]
    for i, m in enumerate(mods):
        tel.record_program("train.step", _Executable(m), n=i)
    assert tel.PROGRAMS_KEPT == 8
    assert not any(m.rendered for m in mods)         # nothing parsed yet
    tables = tel.program_scopes()
    assert [t["labels"]["n"] for t in tables] == list(range(3, 11))
    assert [t["module"] for t in tables] == [f"jit_p{i}"
                                             for i in range(3, 11)]
    tel.program_scopes()
    # rendered once, on the first request, and never the dropped ones
    assert [m.rendered for m in mods] == [0] * 3 + [1] * 8
    with tel._programs_lock:
        assert all(p["hlo"] is None for p in tel._programs)
    tel.reset_programs()
    assert tel.program_scopes() == []


def test_nothing_of_the_model_stays_alive():
    """After the model is gone the tables are still there and no array of
    the model's is: the registry holds host data only."""
    def live():
        gc.collect()
        jax.clear_caches()
        gc.collect()
        return sorted((a.shape, str(a.dtype)) for a in jax.live_arrays())

    before = live()
    tel.reset_programs()
    net = _fit_decoder("ouro_2_6b")
    del net
    assert live() == before
    table, = tel.program_scopes()
    assert len(table["instructions"]) > 100
    assert live() == before


# ------------------------------------------------------ the rule, the parse

@pytest.mark.parametrize("scope,phase", [
    ("while/body/closed_call/jvp(forward)/l0.attn/attn.full/dot_general",
     "forward"),
    ("forward/l0.attn/dot_general", "forward"),
    ("while/body/closed_call/transpose(jvp(forward))/l0.mlp/dot_general",
     "backward"),
    ("transpose(jvp(forward))/while/body/closed_call/checkpoint/"
     "rematted_computation/l0.attn/attn.full/dot_general", "recompute"),
    ("transpose(jvp(forward))/while/body/closed_call/checkpoint/l0.attn/"
     "attn.full/mul", "backward"),
    ("while/body/closed_call/updater/cond/branch_1_fun/add", "updater"),
    ("while/body/closed_call/sentinel/reduce_sum", "sentinel"),
    ("while/body/closed_call/clip/mul", "clip"),
    # a recomputation the updater's scope holds is the updater's
    ("updater/checkpoint/rematted_computation/mul", "updater"),
    ("while/body/closed_call/convert_element_type", "other"),
    ("forwards/dot_general", "other"),
    ("", "other"),
])
def test_the_phase_rule(scope, phase):
    assert tel.scope_phase(scope) == phase


HAND_MADE = '''HloModule jit_step, is_scheduled=true

%fused_wgrad (p0: bf16[8,4], p1: bf16[8,2]) -> (f32[], f32[4,2]) {
  %p0 = bf16[8,4]{1,0} parameter(0)
  %p1 = bf16[8,2]{1,0} parameter(1)
  %convolution.1 = f32[4,2]{1,0} convolution(%p0, %p1), dim_labels=fb_io->bf, metadata={op_name="jit(step)/jit(main)/transpose(jvp(forward))/out/dot_general" source_file="x.py" source_line=3}
  %multiply.2 = f32[4,2]{1,0} multiply(%convolution.1, %convolution.1), metadata={op_name="jit(step)/jit(main)/sentinel/mul"}
  %constant.3 = f32[] constant(0)
  %reduce.4 = f32[] reduce(%multiply.2, %constant.3), dimensions={0,1}, to_apply=%add, metadata={op_name="jit(step)/jit(main)/sentinel/reduce_sum"}
  ROOT %tuple.5 = (f32[], f32[4,2]{1,0}) tuple(%reduce.4, %convolution.1)
}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%body (arg: (s32[], f32[4,2])) -> (s32[], f32[4,2]) {
  %arg = (s32[]{:T(128)}, f32[4,2]{1,0:T(4,128)}) parameter(0)
  %get-tuple-element.7 = f32[4,2]{1,0:T(4,128)} get-tuple-element(%arg), index=1
  %fusion.8 = f32[4,2]{1,0:T(4,128)} fusion(%get-tuple-element.7), kind=kLoop, calls=%fused_fwd, metadata={op_name="jit(step)/jit(main)/jvp(forward)/while/body/hidden/tanh"}
  ROOT %tuple.9 = (s32[]{:T(128)}, f32[4,2]{1,0:T(4,128)}) tuple(%get-tuple-element.7, %fusion.8)
}

%fused_fwd (p: f32[4,2]) -> f32[4,2] {
  %p = f32[4,2]{1,0} parameter(0)
  ROOT %tanh.1 = f32[4,2]{1,0} tanh(%p), metadata={op_name="jit(step)/jit(main)/jvp(forward)/while/body/hidden/tanh"}
}

%fused_unnamed (q: f32[4,2]) -> f32[4,2] {
  %q = f32[4,2]{1,0} parameter(0)
  %negate.1 = f32[4,2]{1,0} negate(%q), metadata={op_name="jit(step)/jit(main)/transpose(jvp(forward))/out/neg"}
  ROOT %bitcast.2 = f32[4,2]{1,0} bitcast(%negate.1)
}

%branch (w: f32[4,2]) -> f32[4,2] {
  %w = f32[4,2]{1,0} parameter(0)
  ROOT %subtract.3 = f32[4,2]{1,0} subtract(%w, %w), metadata={op_name="jit(step)/jit(main)/updater/cond/branch_1_fun/sub"}
}

ENTRY %main.12 (x: bf16[8,4], g: bf16[8,2]) -> f32[4,2] {
  %x = bf16[8,4]{1,0} parameter(0)
  %g = bf16[8,2]{1,0} parameter(1)
  %multiply_reduce_fusion.1 = (f32[]{:T(128)}, f32[4,2]{1,0:T(4,128)}) fusion(%x, %g), kind=kOutput, calls=%fused_wgrad, metadata={op_name="jit(step)/jit(main)/sentinel/reduce_sum"}
  %while.2 = (s32[]{:T(128)}, f32[4,2]{1,0:T(4,128)}) while(%multiply_reduce_fusion.1), condition=%cond, body=%body, metadata={op_name="jit(step)/jit(main)/jvp(forward)/while"}
  %copy.6 = f32[4,2]{0,1} copy(%x)
  %dot.12 = f32[4,2]{1,0} dot(%x, %x), metadata={op_name="jit(step)/jit(main)/jvp(forward)/out/attn.full/checkpoint/...gqd,...kd->...gqk/dot_general"}
  %fusion.10 = f32[4,2]{1,0} fusion(%copy.6), kind=kLoop, calls=%fused_unnamed
  ROOT %conditional.4 = f32[4,2]{1,0} conditional(%while.2, %x, %x), branch_computations={%branch, %branch}, metadata={op_name="jit(step)/jit(main)/updater/cond"}
}
'''


@pytest.fixture
def hand_made():
    tel.reset_programs()
    tel.record_program("train.step", _Executable(_Module("jit_step",
                                                         HAND_MADE)),
                       vertices=["hidden", "out"])
    table, = tel.program_scopes()
    yield table["instructions"]
    tel.reset_programs()


def test_a_weight_gradient_fusion_says_what_rides_it(hand_made):
    """The kernel the trace shows as ``multiply_reduce_fusion f32[]``: named
    after the sentinel's sum, the tuple's first result, with the backward
    pass's product inside (PERF.md, PR 34)."""
    ins = hand_made["multiply_reduce_fusion.1"]
    assert ins["shape"] == "f32[]" and ins["phase"] == "sentinel"
    assert ins["phases_inside"] == ["backward", "sentinel"]


def test_loop_bodies_and_branches_are_listed_fused_insides_are_not(hand_made):
    assert hand_made["fusion.8"] == {
        "shape": "f32[4,2]", "scope": "jvp(forward)/while/body/hidden/tanh",
        "scopes": ["forward", "hidden"], "phase": "forward",
        "phases_inside": ["forward"], "vertex": "hidden"}
    assert hand_made["subtract.3"]["phase"] == "updater"
    assert hand_made["while.2"]["shape"] == "s32[]"
    assert hand_made["while.2"]["phase"] == "forward"
    assert "convolution.1" not in hand_made and "tanh.1" not in hand_made
    # the compiler's own copy names nothing
    assert hand_made["copy.6"] == {"shape": "f32[4,2]", "scope": "",
                                   "scopes": [], "phase": "other",
                                   "phases_inside": [], "vertex": None}


def test_scopes_are_the_programs_own_names(hand_made):
    """Not a transformation, not JAX's structure, not an einsum's
    subscripts, not the primitive."""
    assert hand_made["dot.12"]["scopes"] == ["forward", "out", "attn.full"]
    assert hand_made["subtract.3"]["scopes"] == ["updater"]


def test_a_fusion_without_a_name_takes_the_one_nearest_its_root(hand_made):
    assert hand_made["fusion.10"] == {
        "shape": "f32[4,2]", "scope": "transpose(jvp(forward))/out/neg",
        "scopes": ["forward", "out"], "phase": "backward",
        "phases_inside": ["backward"], "vertex": "out"}


def test_a_table_is_rendered_once_and_then_plain_data(hand_made):
    again, = tel.program_scopes()
    assert again["instructions"] == hand_made
    assert json.loads(json.dumps(again)) == again
