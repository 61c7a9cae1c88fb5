"""ISSUE 15: the staticcheck analyzer itself.

Three layers under test: (1) every Tier A rule against synthetic
positive/negative fixture snippets (parse-from-string, no fixture files
on disk), (2) the suppression/baseline/CLI machinery, (3) the Tier B
jaxpr audit on a real 2-layer model under a bf16 policy — including the
acceptance criterion's deliberately un-hoisted in-scan cast.

The final gate test runs the full analyzer over the shipped package and
asserts ZERO non-baselined findings — the analyzer is a standing tier-1
gate, not a tool someone has to remember to run.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.runtime import staticcheck as sc
from deeplearning4j_tpu.runtime import telemetry as tel


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------- fixtures
# each rule: one snippet that MUST trip it and one that must not


def test_compile_attribution_positive_negative():
    bad = (
        "def warm(self, avals):\n"
        "    exe = jitted.lower(avals).compile()\n"
        "    return exe\n")
    good = (
        "def warm(self, avals):\n"
        "    exe = jitted.lower(avals).compile()\n"
        "    record_compile('serving.engine', 'warmup')\n"
        "    return exe\n")
    helper = (
        "def warm(self, avals):\n"
        "    exe = jitted.lower(avals).compile()\n"
        "    self._record_build('train.step')\n"
        "    return exe\n")
    regex = "import re\n\ndef pat():\n    return re.compile('x+')\n"
    assert rules_of(sc.check_source(bad, rules=["compile-attribution"])) \
        == ["compile-attribution"]
    assert sc.check_source(good, rules=["compile-attribution"]) == []
    assert sc.check_source(helper, rules=["compile-attribution"]) == []
    assert sc.check_source(regex, rules=["compile-attribution"]) == []


def test_compile_cause_registered_positive_negative():
    bad = "record_compile('train.step', 'tpyo_cause')\n"
    bad_kw = "model.invalidate(cause='definitely_not_a_cause')\n"
    good = ("record_compile('train.step', 'warmup')\n"
            "model._invalidate_compiled(cause='dtype_policy')\n")
    computed = "record_compile('train.step', self._consume_cause())\n"
    assert rules_of(sc.check_source(
        bad, rules=["compile-cause-registered"])) \
        == ["compile-cause-registered"]
    assert rules_of(sc.check_source(
        bad_kw, rules=["compile-cause-registered"])) \
        == ["compile-cause-registered"]
    assert sc.check_source(good, rules=["compile-cause-registered"]) == []
    assert sc.check_source(computed,
                           rules=["compile-cause-registered"]) == []


def test_metric_label_blending_positive_negative():
    bad = ('_M = counter("serving.engine.calls", "requests")\n'
           "\n"
           "class Engine:\n"
           "    def __init__(self):\n"
           "        self._m = _M\n")
    good = ('_M = counter("serving.engine.calls", "requests")\n'
            "\n"
            "class Engine:\n"
            "    def __init__(self):\n"
            "        weakref.finalize(self, registry.discard_cells,\n"
            "                         engine=self._id)\n"
            "        self._m = _M.labeled(engine=self._id)\n")
    no_discard = ('_M = counter("serving.engine.calls", "requests")\n'
                  "\n"
                  "class Engine:\n"
                  "    def __init__(self):\n"
                  "        self._m = _M.labeled(engine=self._id)\n")
    read_only = ('wait = histogram("train.phase.data_wait_s")'
                 ".hist_snapshot(window=5)\n")
    other_family = '_M = counter("faults.calls", "per-site trips")\n'
    assert rules_of(sc.check_source(bad, rules=["metric-label-blending"])) \
        == ["metric-label-blending"]
    assert sc.check_source(good, rules=["metric-label-blending"]) == []
    found = sc.check_source(no_discard, rules=["metric-label-blending"])
    assert found and "discard_cells" in found[0].message
    assert sc.check_source(read_only, rules=["metric-label-blending"]) == []
    assert sc.check_source(other_family,
                           rules=["metric-label-blending"]) == []


def test_module_level_code_is_in_scope():
    """Import-time code gets the ``<module>`` pseudo-scope: a
    module-level unattributed compile is a finding, an attributed one is
    not (review-round regression — module statements were invisible)."""
    assert rules_of(sc.check_source(
        "exe = jitted.lower(avals).compile()\n",
        rules=["compile-attribution"])) == ["compile-attribution"]
    assert sc.check_source(
        "exe = jitted.lower(avals).compile()\n"
        "record_compile('init.warm', 'first_build')\n",
        rules=["compile-attribution"]) == []


def test_unknown_chained_method_is_a_finding_not_a_crash():
    """A per-instance declaration chained into an unrecognized method
    must degrade to a conservative finding (review-round regression: it
    crashed the whole run with a TypeError)."""
    found = sc.check_source(
        'x = counter("serving.engine.calls", "h").describe()\n',
        rules=["metric-label-blending"])
    assert rules_of(found) == ["metric-label-blending"]


def test_discard_exemption_is_expression_scoped():
    """Only an instance-label VALUE that reads ``telemetry_label`` (or a
    local assigned from it) waives the discard_cells requirement — a
    comment mentioning the string does not (review-round regression)."""
    comment_only = ('_M = counter("serving.engine.calls", "h")\n'
                    "# telemetry_label (mentioned in prose only)\n"
                    "class E:\n"
                    "    def __init__(self):\n"
                    "        self._m = _M.labeled(engine=self._id)\n")
    found = sc.check_source(comment_only, rules=["metric-label-blending"])
    assert any("discard_cells" in f.message for f in found)
    direct = ('_M = counter("train.phase.step_s", "h")\n'
              "class E:\n"
              "    def clocks(self):\n"
              "        return _M.labeled(model=self.telemetry_label)\n")
    assert sc.check_source(direct, rules=["metric-label-blending"]) == []
    via_local = ('_M = counter("train.phase.step_s", "h")\n'
                 "class E:\n"
                 "    def clocks(self):\n"
                 "        lbl = getattr(self, 'telemetry_label', None)\n"
                 "        return _M.labeled(model=lbl)\n")
    assert sc.check_source(via_local, rules=["metric-label-blending"]) == []


def test_registry_lock_discipline_positive_negative():
    bad = ("def bump(m, n):\n"
           "    m.set((m.value(default=0) or 0) + n)\n")
    good = ("def bump(m, n):\n"
            "    with registry.locked():\n"
            "        m.set((m.value(default=0) or 0) + n)\n")
    bad_zero = ("def reset_set(m, v):\n"
                "    m.zero()\n"
                "    m.inc(v)\n")
    good_zero = ("def reset_set(m, v):\n"
                 "    with registry.locked():\n"
                 "        m.zero()\n"
                 "        m.inc(v)\n")
    plain = "def bump(m, n):\n    m.inc(n)\n"
    assert rules_of(sc.check_source(
        bad, rules=["registry-lock-discipline"])) \
        == ["registry-lock-discipline"]
    assert sc.check_source(good, rules=["registry-lock-discipline"]) == []
    assert rules_of(sc.check_source(
        bad_zero, rules=["registry-lock-discipline"])) \
        == ["registry-lock-discipline"]
    assert sc.check_source(good_zero,
                           rules=["registry-lock-discipline"]) == []
    assert sc.check_source(plain, rules=["registry-lock-discipline"]) == []


def test_host_sync_in_hot_path_positive_negative():
    # the rule is scoped by the HOT_PATHS site map: same code outside a
    # mapped (file, function) pair is not a finding
    bad = ("class Net:\n"
           "    def fit(self, data):\n"
           "        for ds in data:\n"
           "            out = self._train_step(ds)\n"
           "            self._score = float(out)\n")
    item = ("class Net:\n"
            "    def fit(self, data):\n"
            "        for ds in data:\n"
            "            out = self._train_step(ds)\n"
            "            self._score = out[0].item()\n")
    good = ("class Net:\n"
            "    def fit(self, data):\n"
            "        for ds in data:\n"
            "            x = np.asarray(ds.features)\n"
            "            out = self._train_step(x)\n"
            "            self._score = out\n")
    assert rules_of(sc.check_source(bad, rel="fix/nn/model.py",
                                    rules=["host-sync-in-hot-path"])) \
        == ["host-sync-in-hot-path"]
    assert rules_of(sc.check_source(item, rel="fix/nn/model.py",
                                    rules=["host-sync-in-hot-path"])) \
        == ["host-sync-in-hot-path"]
    assert sc.check_source(good, rel="fix/nn/model.py",
                           rules=["host-sync-in-hot-path"]) == []
    # unmapped function/file: no findings even for the bad snippet
    assert sc.check_source(bad, rel="fix/nn/other.py",
                           rules=["host-sync-in-hot-path"]) == []


def test_nondeterminism_in_compiled_positive_negative():
    bad_time = ("def _build_train_step(self):\n"
                "    def step_fn(params):\n"
                "        return params * time.time()\n"
                "    return jax.jit(step_fn)\n")
    bad_np = ("def _build_train_step(self):\n"
              "    noise = np.random.normal(size=4)\n"
              "    return jax.jit(lambda p: p + noise)\n")
    good = ("def _build_train_step(self):\n"
            "    def step_fn(params, key):\n"
            "        k1, k2 = jax.random.split(key)\n"
            "        return params\n"
            "    return jax.jit(step_fn)\n")
    # the shared core's bodies (nn/trainstep.py) are walked like the
    # engines' builders that wrap them
    bad_core = ("def gradient_tail(updater, clip):\n"
                "    def tail(loss, grads):\n"
                "        return grads * time.time()\n"
                "    return tail\n")
    outside = "def fit(self):\n    t0 = time.time()\n"
    assert rules_of(sc.check_source(
        bad_time, rules=["nondeterminism-in-compiled"])) \
        == ["nondeterminism-in-compiled"]
    assert rules_of(sc.check_source(
        bad_np, rules=["nondeterminism-in-compiled"])) \
        == ["nondeterminism-in-compiled"]
    assert rules_of(sc.check_source(
        bad_core, rules=["nondeterminism-in-compiled"])) \
        == ["nondeterminism-in-compiled"]
    assert sc.check_source(good, rules=["nondeterminism-in-compiled"]) == []
    assert sc.check_source(outside,
                           rules=["nondeterminism-in-compiled"]) == []


def test_fault_site_registration_positive_negative():
    bad = "faults.trip('serving.bogus_site')\n"
    good = "faults.trip('train.step')\n"
    dynamic = "faults.trip(site_var)\n"
    assert rules_of(sc.check_source(
        bad, rules=["fault-site-registration"])) \
        == ["fault-site-registration"]
    assert sc.check_source(good, rules=["fault-site-registration"]) == []
    assert sc.check_source(dynamic, rules=["fault-site-registration"]) == []


def test_fleet_version_label_positive_negative():
    """ISSUE 20 satellite: serving cells recorded from fleet-managed code
    must carry version= at EVERY binding site — two versions of one model
    must never blend into one p99 during a canary."""
    bad = ('_H = histogram("serving.fleet.request_latency_s", "lat")\n'
           "\n"
           "class V:\n"
           "    def __init__(self):\n"
           "        self._h = _H.labeled(model=self.name, pool='fleet')\n")
    good = ('_H = histogram("serving.fleet.request_latency_s", "lat")\n'
            "\n"
            "class V:\n"
            "    def __init__(self):\n"
            "        self._h = _H.labeled(model=self.name,\n"
            "                             version=str(self.version),\n"
            "                             pool='fleet')\n")
    assert rules_of(sc.check_source(bad, rules=["fleet-version-label"])) \
        == ["fleet-version-label"]
    assert sc.check_source(good, rules=["fleet-version-label"]) == []
    # chained writes: the version obligation holds for direct inc() too
    chain_bad = ('counter("serving.fleet.swap_events", "e")'
                 '.inc(model="m", event="loaded")\n')
    chain_good = ('counter("serving.fleet.swap_events", "e")'
                  '.inc(model="m", version="1", event="loaded")\n')
    assert rules_of(sc.check_source(chain_bad,
                                    rules=["fleet-version-label"])) \
        == ["fleet-version-label"]
    assert sc.check_source(chain_good, rules=["fleet-version-label"]) == []
    # reads never create cells; a declaration with NO binding site at all
    # is itself a finding (an unbindable fleet cell cannot carry version=)
    read_only = ('p = histogram("serving.fleet.request_latency_s", "l")'
                 ".percentile(99)\n")
    assert sc.check_source(read_only, rules=["fleet-version-label"]) == []
    unbound = '_M = counter("serving.fleet.routed", "r")\n'
    assert rules_of(sc.check_source(unbound,
                                    rules=["fleet-version-label"])) \
        == ["fleet-version-label"]
    # outside fleet modules, non-fleet serving families are exempt ...
    other = ('_M = counter("serving.engine.calls", "c")\n'
             "\n"
             "class E:\n"
             "    def __init__(self):\n"
             "        self._m = _M.labeled(engine=self._id)\n")
    assert sc.check_source(other, rules=["fleet-version-label"]) == []
    # ... but INSIDE serving/fleet.py every serving.* cell is versioned
    assert rules_of(sc.check_source(other, rel="serving/fleet.py",
                                    rules=["fleet-version-label"])) \
        == ["fleet-version-label"]


def test_fleet_version_label_suppression():
    src = ('_H = histogram("serving.fleet.request_latency_s", "lat")\n'
           "\n"
           "class V:\n"
           "    def __init__(self):\n"
           "        # staticcheck: disable=fleet-version-label -- "
           "aggregate-only cell, no per-version split\n"
           "        self._h = _H.labeled(model=self.name, pool='fleet')\n")
    assert sc.check_source(src, rules=["fleet-version-label"]) == []


# ------------------------------------------------- suppressions + baseline


def test_suppression_with_reason_suppresses():
    src = ("def warm(self, avals):\n"
           "    # staticcheck: disable=compile-attribution -- warmup-only"
           " helper, caller records\n"
           "    exe = jitted.lower(avals).compile()\n"
           "    return exe\n")
    assert sc.check_source(src, rules=["compile-attribution"]) == []


def test_suppression_without_reason_is_a_finding():
    src = ("def warm(self, avals):\n"
           "    # staticcheck: disable=compile-attribution\n"
           "    exe = jitted.lower(avals).compile()\n"
           "    return exe\n")
    found = sc.check_source(src, rules=["compile-attribution"])
    assert rules_of(found) == ["bad-suppression"]


def test_suppression_wrong_rule_does_not_suppress():
    src = ("def warm(self, avals):\n"
           "    # staticcheck: disable=fault-site-registration -- nope\n"
           "    exe = jitted.lower(avals).compile()\n"
           "    return exe\n")
    assert rules_of(sc.check_source(src, rules=["compile-attribution"])) \
        == ["compile-attribution"]


def test_baseline_round_trip(tmp_path):
    src = ("def warm(self, avals):\n"
           "    exe = jitted.lower(avals).compile()\n"
           "    return exe\n")
    sources = {"pkg/mod.py": src}
    rep = sc.run(sources=sources, rules=["compile-attribution"],
                 baseline_path=str(tmp_path / "absent.json"))
    assert len(rep.findings) == 1 and not rep.baselined
    f = rep.findings[0]
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"entries": [
        {"rule": f.rule, "path": f.path, "match": "AOT-compiles",
         "reason": "fixture: grandfathered for the round-trip test"}]}))
    rep2 = sc.run(sources=sources, rules=["compile-attribution"],
                  baseline_path=str(bl))
    assert rep2.findings == [] and len(rep2.baselined) == 1
    assert rep2.baselined[0][1]["reason"].startswith("fixture")
    assert rep2.stale_baseline == []
    # the entry goes stale when the violation is fixed — reported, not fatal
    rep3 = sc.run(sources={"pkg/mod.py": "x = 1\n"},
                  rules=["compile-attribution"], baseline_path=str(bl))
    assert rep3.findings == [] and len(rep3.stale_baseline) == 1


def test_baseline_requires_reason(tmp_path):
    bl = tmp_path / "bad.json"
    bl.write_text(json.dumps({"entries": [
        {"rule": "compile-attribution", "path": "x.py", "match": ""}]}))
    with pytest.raises(ValueError, match="reason"):
        sc.load_baseline(str(bl))


def test_shipped_baseline_entries_all_carry_reasons():
    for e in sc.load_baseline():  # ValueError on a reasonless entry
        assert str(e["reason"]).strip()


# --------------------------------------------------------------------- CLI


def test_cli_json_schema(capsys):
    rc = sc.main(["--format", "json"])
    out = capsys.readouterr().out
    doc = json.loads(out)
    assert doc["version"] == 1
    assert set(doc) >= {"rules", "findings", "baselined", "suppressed",
                        "stale_baseline", "counts"}
    assert len(doc["rules"]) >= 6
    for f in doc["findings"] + doc["baselined"]:
        assert set(f) >= {"rule", "path", "line", "message"}
    for f in doc["baselined"]:
        assert str(f["reason"]).strip()
    # the shipped tree is the gate: CLI exit 0 = no open findings
    assert rc == 0 and doc["findings"] == []


def test_cli_text_and_list_rules(capsys):
    assert sc.main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    for name in ("compile-attribution", "metric-label-blending",
                 "registry-lock-discipline", "host-sync-in-hot-path",
                 "nondeterminism-in-compiled", "fault-site-registration",
                 "compile-cause-registered"):
        assert name in listed
    assert sc.main([]) == 0
    txt = capsys.readouterr().out
    assert "0 open finding(s)" in txt
    assert sc.main(["--rules", "no-such-rule"]) == 2


def test_run_counts_findings_into_telemetry():
    runs = tel.registry.get("staticcheck.runs")
    findings = tel.registry.get("staticcheck.findings")
    r0 = runs.total()
    bad = "record_compile('train.step', 'tpyo_cause')\n"
    before = findings.total()
    rep = sc.run(sources={"m.py": bad}, rules=["compile-cause-registered"],
                 baseline_path="/nonexistent/baseline.json")
    assert len(rep.findings) == 1
    assert runs.total() == r0 + 1
    assert findings.total() == before + 1


# ------------------------------------------------------- Tier B: jaxpr audit


def _bf16_net():
    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Sgd
    conf = (NeuralNetConfiguration.builder().seed(7).data_type("BFLOAT16")
            .updater(Sgd(learning_rate=0.1))
            .input_type(InputType.feed_forward(12))
            .list(DenseLayer(n_out=24, activation="tanh"),
                  OutputLayer(n_out=4)).build())
    return MultiLayerNetwork(conf).init()


def test_audit_compiled_clean_on_real_bf16_model():
    """The shipped train step under a bf16 policy passes all four Tier B
    rules — incl. donation-applied (the step donates params/opt/bn) and
    no-f32-leak (every dot contracts bf16)."""
    net = _bf16_net()
    assert net.audit_compiled(16, accum_steps=4) == []
    assert net.audit_compiled(8) == []


def test_audit_catches_unhoisted_in_scan_cast(monkeypatch):
    """Acceptance criterion: a deliberately un-hoisted master->compute
    cast inside the microbatch scan (the r12 bug, forced by faking a
    regularization term) trips no-param-cast-in-scan."""
    net = _bf16_net()
    monkeypatch.setattr(type(net), "_uses_regularization",
                        lambda self: True)
    found = net.audit_compiled(16, accum_steps=4)
    assert rules_of(found) == ["no-param-cast-in-scan"]
    # param shapes are named in the message so the finding is actionable
    assert any("(12, 24)" in f.message for f in found)


def test_jaxpr_audit_catches_host_callback():
    def f(x):
        jax.debug.callback(lambda v: None, x)
        return x + 1

    found = sc.jaxpr_audit(jax.jit(f), (jnp.ones(4),),
                           rules=["no-host-callback"])
    assert rules_of(found) == ["no-host-callback"]


def test_jaxpr_audit_catches_missing_donation():
    f = jax.jit(lambda x: x + 1)  # nothing donated
    found = sc.jaxpr_audit(
        f, (jax.ShapeDtypeStruct((4,), jnp.float32),),
        rules=["donation-applied"], expect_donation=True)
    assert rules_of(found) == ["donation-applied"]
    g = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    assert sc.jaxpr_audit(
        g, (jax.ShapeDtypeStruct((4,), jnp.float32),),
        rules=["donation-applied"], expect_donation=True) == []


def test_jaxpr_audit_catches_f32_leak_under_bf16():
    f = jax.jit(lambda a, b: a @ b)
    avals = (jax.ShapeDtypeStruct((4, 8), jnp.float32),
             jax.ShapeDtypeStruct((8, 2), jnp.float32))
    found = sc.jaxpr_audit(f, avals, policy="BFLOAT16",
                           rules=["no-f32-leak-under-bf16-policy"])
    assert rules_of(found) == ["no-f32-leak-under-bf16-policy"]
    # under an f32 policy the same program is fine
    assert sc.jaxpr_audit(f, avals, policy="FLOAT",
                          rules=["no-f32-leak-under-bf16-policy"]) == []


def test_jaxpr_audit_scan_scoping():
    """The cast rule only fires INSIDE loop bodies — a legitimate
    once-per-step cast outside the scan (the hoisted program) is not a
    finding even though shape+dtype match."""
    shape = (6, 6)

    def hoisted(p, xs):
        p16 = p.astype(jnp.bfloat16)
        return jax.lax.scan(lambda c, x: (c + (p16 * x).sum(), None),
                            jnp.bfloat16(0), xs)[0]

    def unhoisted(p, xs):
        return jax.lax.scan(
            lambda c, x: (c + (p.astype(jnp.bfloat16) * x).sum(), None),
            jnp.bfloat16(0), xs)[0]

    args = (jnp.ones(shape, jnp.float32), jnp.ones((3,) + shape,
                                                   jnp.bfloat16))
    ok = sc.jaxpr_audit(jax.jit(hoisted), args, param_shapes=[shape],
                        rules=["no-param-cast-in-scan"])
    bad = sc.jaxpr_audit(jax.jit(unhoisted), args, param_shapes=[shape],
                         rules=["no-param-cast-in-scan"])
    assert ok == []
    assert rules_of(bad) == ["no-param-cast-in-scan"]


# ------------------------------------------------------------- the gate


def test_zz_gate_zero_open_findings_on_shipped_tree():
    """THE standing gate (acceptance): the full rule set over the shipped
    package yields zero non-baselined findings, every baselined finding
    carries a reason, and no baseline entry is stale."""
    rep = sc.run()
    assert rep.findings == [], "\n".join(str(f) for f in rep.findings)
    for f, e in rep.baselined:
        assert str(e["reason"]).strip(), f
    assert rep.stale_baseline == [], rep.stale_baseline
    # ratchet: ISSUE 20 landed fleet-version-label as the 10th rule
    assert len(rep.rules) >= 10
