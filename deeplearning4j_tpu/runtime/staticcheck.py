"""JAX-aware static analysis: the repo's hand-enforced invariants as
machine-checked rules (ISSUE 15).

Five review rounds (r11-r18, CHANGES.md) kept re-finding the same defect
classes by hand: unattributed ``lower().compile()`` sites fragmenting the
retrace dashboards, per-instance metric cells missing their
``engine=``/``pi=``/``model=`` labels (the anti-blending rule),
read-modify-writes on registry cells outside ``registry.locked()``, and
param-shaped dtype casts leaking back inside compiled scan bodies. This
module turns each of those into an automated program check, in two tiers:

**Tier A — AST lint** (:func:`run`, ``python -m
deeplearning4j_tpu.runtime.staticcheck``, ``make lint``): a rule registry
walking every package module's AST once (parse results are cached by
mtime, so the lint gate and the zz coverage floor's metric-name
cross-check share a single walk per suite run). Rules:

- ``compile-attribution`` — a function that AOT-compiles
  (``...lower(...).compile()``) must report the event to the retrace
  tracker (``record_compile``/``_record_build`` in the same function),
  or every compile it performs is invisible to the zero-recompile
  steady-state dashboards.
- ``compile-cause-registered`` — every literal ``cause=`` handed to
  ``record_compile``/``invalidate``/``_invalidate_compiled`` must be in
  ``telemetry.COMPILE_CAUSES`` (a typo'd cause silently fragments the
  dashboards). Absorbs ``tests/test_static_telemetry.py``'s collectors.
- ``metric-label-blending`` — ``counter``/``gauge``/``histogram``
  declarations in the per-instance families (``serving.*``,
  ``train.phase.*``, ``parallel.overlap.*``, ``checkpoint.*``) must be
  bound with an instance label (``engine=``/``pi=``/``model=``/``ckpt=``)
  somewhere in the package, and a module binding instance cells must have
  a ``discard_cells`` finalizer site (or inherit the
  ``telemetry_label`` finalizer) so instance churn cannot grow /metrics.
- ``pool-scoped-metric-label`` — ``serving.*`` cells must additionally
  bind ``pool=<role>`` beside the instance label (ISSUE 18): one scrape
  collects a disaggregated prefill/decode process pair, and an
  unlabeled-pool cell blends both roles' telemetry.
- ``fleet-version-label`` — fleet-managed serving cells (the
  ``serving.fleet.*`` family, plus any ``serving.*`` declaration inside
  ``serving/fleet.py``) must bind ``version=<v>`` beside their instance/
  pool labels (ISSUE 20): the fleet runs N versions of one model
  concurrently, and an unversioned cell blends the incumbent's p99 with
  the canary's — the exact signal promotion/rollback decides on.
- ``registry-lock-discipline`` — a read-modify-write of a registry cell
  (``.set(... .value() ...)``, ``.zero()``-then-``.inc()``, cross-kind
  shims) must sit inside a ``registry.locked()``/``_lock`` context.
- ``host-sync-in-hot-path`` — ``float()``/``.item()``/``np.asarray()``
  on step outputs inside the fit-loop / serving-dispatcher hot paths
  (:data:`HOT_PATHS`) blocks the async dispatch pipeline.
- ``nondeterminism-in-compiled`` — ``time.*``/``random.*``/``np.random``
  reachable from the train-step / engine builder functions
  (:data:`BUILDER_FUNCS`) would bake a host value into a compiled
  program (retrace-per-step, or worse: silent SPMD divergence).
- ``fault-site-registration`` — every literal site handed to
  ``faults.trip()``/``inject()``/``clear()`` must be in ``faults.SITES``
  (an unregistered site raises at runtime — but only on the code path
  that trips it, which is exactly the path nobody runs).

Findings carry ``(rule, path, line, message)``. Inline suppressions:
``# staticcheck: disable=<rule>[,<rule>] -- <reason>`` on the flagged
line or the line above; the reason is MANDATORY (a reasonless suppression
is itself a ``bad-suppression`` finding). Grandfathered violations live
in a checked-in JSON baseline (``staticcheck_baseline.json`` at the repo
root) where every entry carries a ``reason`` string; the CLI exits
non-zero on any non-baselined finding and warns on stale baseline
entries so the baseline only ever ratchets down.

**Tier B — compiled-program audit** (:func:`jaxpr_audit`,
:func:`audit_model`, ``model.audit_compiled()``): generalizes the r12/r18
one-off jaxpr regressions into reusable checks on the REAL built train
steps — no param-shaped ``convert_element_type`` inside scan bodies
(``no-param-cast-in-scan``), no host callbacks (``no-host-callback``),
donation actually applied in the lowered program
(``donation-applied``), and no f32 matmuls/convs under a 16-bit compute
policy (``no-f32-leak-under-bf16-policy``).

Telemetry: ``staticcheck.findings{rule=,state=}`` counts every finding a
:func:`run` discovers (state=open|baselined) and ``staticcheck.runs``
counts analyzer runs — bench artifacts embed the snapshot so every
benchmark records the lint state it ran under.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import os
import re
import sys
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from . import telemetry as _tel

# ---------------------------------------------------------------- findings

_M_FINDINGS = _tel.counter(
    "staticcheck.findings",
    "lint findings by rule= and state= (open|baselined) per analyzer run")
_M_RUNS = _tel.counter("staticcheck.runs", "staticcheck analyzer runs")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""
    rule: str
    path: str          # package-relative, forward slashes
    line: int
    message: str

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


# ----------------------------------------------------------- module index

#: ``# staticcheck: disable=rule1,rule2 -- reason`` (reason mandatory)
_SUPPRESS_RE = re.compile(
    r"#\s*staticcheck:\s*disable=(?P<rules>[\w\-*,\s]+?)"
    r"(?:\s*--\s*(?P<reason>.*\S))?\s*$")


class ModuleIndex:
    """One parsed module: AST + source lines + suppression table. Parsing
    is the expensive half of the walk, so instances are cached by
    (path, mtime) — the lint gate, the migrated telemetry collectors and
    the zz coverage floor all share one parse per file per run."""

    def __init__(self, source: str, path: str, rel: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, path)
        # line -> (set of rule names or {"*"}, reason or None)
        self.suppressions: Dict[int, Tuple[set, Optional[str]]] = {}
        for i, line in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(line)
            if m:
                rules = {r.strip() for r in m.group("rules").split(",")
                         if r.strip()}
                self.suppressions[i] = (rules, m.group("reason"))

    def suppression_for(self, finding: Finding):
        """The suppression covering ``finding`` (its line, or a
        standalone comment line directly above), or None."""
        for ln in (finding.line, finding.line - 1):
            entry = self.suppressions.get(ln)
            if entry is None:
                continue
            rules, reason = entry
            if ln == finding.line - 1:
                # the line above only counts when it is comment-only —
                # a suppression trailing unrelated code stays local
                code = self.lines[ln - 1].strip()
                if not code.startswith("#"):
                    continue
            if "*" in rules or finding.rule in rules:
                return ln, rules, reason
        return None


_INDEX_CACHE: Dict[str, Tuple[float, ModuleIndex]] = {}


def _pkg_dir() -> str:
    from .. import __file__ as pkg_file
    return os.path.dirname(pkg_file)


def repo_root() -> str:
    return os.path.dirname(_pkg_dir())


def index_file(path: str, root: Optional[str] = None) -> ModuleIndex:
    root = root or repo_root()
    rel = os.path.relpath(path, root).replace(os.sep, "/")
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        mtime = -1.0
    cached = _INDEX_CACHE.get(path)
    if cached is not None and cached[0] == mtime:
        return cached[1]
    with open(path, "r", encoding="utf-8") as f:
        idx = ModuleIndex(f.read(), path, rel)
    _INDEX_CACHE[path] = (mtime, idx)
    return idx


def index_source(source: str, rel: str = "<fixture>") -> ModuleIndex:
    """Parse a source STRING into an uncached index — the test fixtures'
    entry point (synthetic positive/negative snippets, no files on
    disk)."""
    return ModuleIndex(source, rel, rel)


def package_files() -> List[str]:
    out = []
    for root, _dirs, files in os.walk(_pkg_dir()):
        for fn in sorted(files):
            if fn.endswith(".py"):
                out.append(os.path.join(root, fn))
    return out


def package_index() -> List[ModuleIndex]:
    return [index_file(p) for p in package_files()]


# ------------------------------------------------------------ AST helpers


def _call_name(node: ast.Call) -> Optional[str]:
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    if isinstance(node.func, ast.Name):
        return node.func.id
    return None


def _unparse(node) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return ""


def _first_literal_arg(node: ast.Call):
    if node.args and isinstance(node.args[0], ast.Constant):
        return node.args[0].value
    return None


def _kw_literal(node: ast.Call, name: str):
    for kw in node.keywords:
        if kw.arg == name and isinstance(kw.value, ast.Constant):
            return kw.value.value
    return None


def _function_scopes(tree: ast.Module):
    """Outermost function scopes (module-level defs and class methods —
    nested defs belong to their enclosing scope) + a pseudo-scope named
    ``<module>`` holding the module-level statements, so import-time
    code (an unattributed module-level compile, a module-level registry
    RMW) is checked too."""
    scopes = []

    def visit(body, qualname):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append((f"{qualname}{node.name}", node))
            elif isinstance(node, ast.ClassDef):
                visit(node.body, f"{qualname}{node.name}.")
    visit(tree.body, "")
    mod = ast.Module(
        body=[s for s in tree.body
              if not isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.ClassDef))],
        type_ignores=[])
    mod.name = "<module>"
    scopes.append(("<module>", mod))
    return scopes


# ---------------------------------------------------------- rule registry


RULES: Dict[str, "Rule"] = {}


@dataclasses.dataclass(frozen=True)
class Rule:
    name: str
    help: str
    check: Callable[[ModuleIndex], Iterable[Finding]]


def rule(name: str, help: str):
    def deco(fn):
        RULES[name] = Rule(name, help, fn)
        return fn
    return deco


# ------------------------------------------------ rule: compile-attribution

#: function names whose job IS the raw lower+compile — the record_compile
#: responsibility sits with their callers (the builders/warmup sites that
#: know the cause), so a compile inside them is not a finding there.
_COMPILE_HELPER_ATTRS = ("_record_build",)

#: functions that reach, through JAX's lowering cache, the executable of a
#: program a build site has already attributed (telemetry.record_dispatch:
#: the call before it compiled the program, this compiles nothing more)
_COMPILE_REUSE_FUNCS = ("record_dispatch",)


@rule("compile-attribution",
      "every function that AOT-compiles (.lower(...).compile()) must "
      "record_compile/_record_build in the same function, or its compiles "
      "are invisible to the retrace tracker")
def _check_compile_attribution(idx: ModuleIndex):
    def compile_calls(sub):
        for node in ast.walk(sub):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "compile" and not node.args and \
                    not node.keywords:
                base = node.func.value
                # `re.compile(...)` always takes args, so arg-less
                # `.compile()` is the XLA AOT call; still skip an
                # explicit `re.compile` spelled weirdly
                if isinstance(base, ast.Name) and base.id in ("re", "_re"):
                    continue
                yield node

    def records(sub) -> bool:
        for node in ast.walk(sub):
            if isinstance(node, ast.Call) and _call_name(node) in (
                    "record_compile",) + _COMPILE_HELPER_ATTRS:
                return True
        return False

    for qual, fn in _function_scopes(idx.tree):
        sites = list(compile_calls(fn))
        if not sites or records(fn) or \
                qual.rsplit(".", 1)[-1] in _COMPILE_REUSE_FUNCS:
            continue
        for node in sites:
            yield Finding(
                "compile-attribution", idx.rel, node.lineno,
                f"{qual}() AOT-compiles but never calls record_compile — "
                "attribute the compile (cause= from COMPILE_CAUSES) or "
                "it fragments the zero-recompile dashboards")


# -------------------------------------------- rule: compile-cause-registered


@rule("compile-cause-registered",
      "literal cause= on record_compile/invalidate/_invalidate_compiled "
      "must be registered in telemetry.COMPILE_CAUSES")
def _check_compile_causes(idx: ModuleIndex):
    causes = set(_tel.COMPILE_CAUSES)
    for node in ast.walk(idx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if name == "record_compile":
            cause = None
            if len(node.args) >= 2 and isinstance(node.args[1],
                                                  ast.Constant):
                cause = node.args[1].value
            else:
                cause = _kw_literal(node, "cause")
            if isinstance(cause, str) and cause not in causes:
                yield Finding(
                    "compile-cause-registered", idx.rel, node.lineno,
                    f"record_compile cause {cause!r} is not in "
                    "COMPILE_CAUSES — register it or fix the typo")
        elif name in ("invalidate", "_invalidate_compiled"):
            cause = _kw_literal(node, "cause")
            if isinstance(cause, str) and cause not in causes:
                yield Finding(
                    "compile-cause-registered", idx.rel, node.lineno,
                    f"invalidate cause {cause!r} is not in COMPILE_CAUSES "
                    "— invalidation causes become compile-event causes "
                    "verbatim (the stale-bucket attribution contract)")


# ---------------------------------------------- rule: metric-label-blending

#: metric-name families whose cells are per-instance surfaces — a write
#: without an instance label blends concurrent engines/models into one
#: cell (the anti-blending rule, r11).
PER_INSTANCE_FAMILIES = ("serving.", "train.phase.", "parallel.overlap.",
                         "checkpoint.")
#: label keys that individuate an instance (host= alone only splits pods)
INSTANCE_LABEL_KEYS = ("engine", "pi", "model", "ckpt")
#: chained methods that only READ a metric — reads cannot create an
#: unlabeled cell, so a read-side lookup needs no binding of its own
_READ_METHODS = ("percentile", "hist_snapshot", "value", "series", "total",
                 "snapshot", "cells")
_WRITE_METHODS = ("labeled", "observe", "observe_many", "inc", "set")


def _has_instance_kw(call: ast.Call) -> bool:
    return any(kw.arg in INSTANCE_LABEL_KEYS for kw in call.keywords)


def _metric_decls(idx: ModuleIndex):
    """(call, name, assigned_var, chained_call) for every literal
    counter/gauge/histogram declaration in per-instance families."""
    # parent links for chain/assign detection, built once per module
    parents = {}
    for node in ast.walk(idx.tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    for node in ast.walk(idx.tree):
        if not isinstance(node, ast.Call) or \
                _call_name(node) not in ("counter", "gauge", "histogram"):
            continue
        name = _first_literal_arg(node)
        if not isinstance(name, str) or \
                not name.startswith(PER_INSTANCE_FAMILIES):
            continue
        assigned = None
        chained = None
        p = parents.get(node)
        if isinstance(p, ast.Attribute):   # counter("...").labeled(...)
            pc = parents.get(p)
            if isinstance(pc, ast.Call):
                chained = (p.attr, pc)
        elif isinstance(p, ast.Assign) and len(p.targets) == 1 and \
                isinstance(p.targets[0], ast.Name):
            assigned = p.targets[0].id
        elif isinstance(p, (ast.Dict, ast.DictComp)):
            pass  # dynamic families (sentinel gauges) — name not literal
        yield node, name, assigned, chained


def _module_binding_sites(idx: ModuleIndex) -> List[Tuple[str, ast.Call]]:
    """[(base_expr_source, call)] for every write-method call with an
    explicit instance label kwarg in the module. Computed once per
    :class:`ModuleIndex` (which is itself mtime-cached), so the
    cross-module lookup below is a list scan, not a repeated AST walk —
    the 'one walk per suite run' contract holds for this rule too."""
    cached = getattr(idx, "_binding_sites", None)
    if cached is not None:
        return cached
    sites = []
    for node in ast.walk(idx.tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in _WRITE_METHODS and \
                _has_instance_kw(node):
            sites.append((_unparse(node.func.value), node))
    idx._binding_sites = sites
    return sites


def _instance_binding_sites(indexes: Sequence[ModuleIndex], var: str):
    """Calls anywhere in ``indexes`` that bind/write metric ``var`` with
    an explicit instance label kwarg."""
    for other in indexes:
        for base, node in _module_binding_sites(other):
            if base == var or base.endswith("." + var):
                yield other, node


def _binding_exempt_from_discard(idx: ModuleIndex, node: ast.Call) -> bool:
    """Whether an instance-labeled binding rides the mixin-owned
    ``telemetry_label`` (whose weakref finalizer lives in
    runtime/sentinel.py) instead of needing a module-local
    ``discard_cells`` site. Checked per binding, on EXPRESSIONS only —
    the instance kwarg's value mentions ``telemetry_label`` directly, or
    names a local that the enclosing function assigns from a
    ``telemetry_label`` read (a comment mentioning the string exempts
    nothing)."""
    values = [kw.value for kw in node.keywords
              if kw.arg in INSTANCE_LABEL_KEYS]
    for v in values:
        if "telemetry_label" in _unparse(v):
            return True
    names = {v.id for v in values if isinstance(v, ast.Name)}
    if not names:
        return False
    for _qual, fn in _function_scopes(idx.tree):
        lo = getattr(fn, "lineno", 1)
        hi = getattr(fn, "end_lineno", lo) or lo
        if not (lo <= node.lineno <= hi):
            continue
        for n in ast.walk(fn):
            if isinstance(n, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id in names
                    for t in n.targets) and \
                    "telemetry_label" in _unparse(n.value):
                return True
    return False


def _check_metric_labels_in(idx: ModuleIndex,
                            indexes: Sequence[ModuleIndex]):
    # (lineno, metric name, binding call) of instance bindings THIS
    # module performs — they oblige it to have a discard_cells site
    needs_discard: List[Tuple[int, str, ast.Call]] = []
    for call, name, assigned, chained in _metric_decls(idx):
        if chained is not None:
            attr, chain_call = chained
            if attr in _READ_METHODS:
                continue  # read-side lookup, creates no cell
            if attr in _WRITE_METHODS and _has_instance_kw(chain_call):
                needs_discard.append((call.lineno, name, chain_call))
                continue
            # a write without an instance kwarg, or an unrecognized
            # chained method: the declaration is not instance-bound here
            yield Finding(
                "metric-label-blending", idx.rel, call.lineno,
                f"per-instance metric {name!r} is used without an "
                f"instance label ({'/'.join(INSTANCE_LABEL_KEYS)}) — "
                "concurrent instances will blend into one cell")
            continue
        if assigned is None:
            # bare declaration statement: nothing binds it here or ever
            yield Finding(
                "metric-label-blending", idx.rel, call.lineno,
                f"per-instance metric {name!r} declared but never bound "
                "with an instance label")
            continue
        sites = list(_instance_binding_sites(indexes, assigned))
        if not sites:
            yield Finding(
                "metric-label-blending", idx.rel, call.lineno,
                f"per-instance metric {name!r} (as {assigned}) is never "
                f"bound with an instance label "
                f"({'/'.join(INSTANCE_LABEL_KEYS)}) anywhere in the "
                "package — concurrent instances will blend")
        for site_idx, site in sites:
            if site_idx.rel == idx.rel:
                needs_discard.append((site.lineno, name, site))
    # a module that BINDS instance cells must also reclaim them — unless
    # every binding rides the mixin-owned telemetry_label, whose
    # finalizer lives in runtime/sentinel.py (checked per binding on
    # expressions, not by substring-grepping the module)
    if "discard_cells" not in idx.source:
        for lineno, name, site in needs_discard:
            if _binding_exempt_from_discard(idx, site):
                continue
            yield Finding(
                "metric-label-blending", idx.rel, lineno,
                f"module binds per-instance cells ({name!r}) but has no "
                "discard_cells finalizer site — instance churn grows "
                "/metrics unboundedly")
            break  # one module-level finding is enough


@rule("metric-label-blending",
      "per-instance metric families must be bound with an instance label "
      "and have a discard_cells finalizer site in the binding module")
def _check_metric_labels(idx: ModuleIndex):
    # package-wide index for cross-module bindings (overlap.py declares,
    # data_parallel.py binds); fixture indexes (no file on disk) check
    # only themselves
    try:
        indexes = package_index() if os.path.exists(idx.path) else [idx]
    except Exception:
        indexes = [idx]
    if idx not in indexes:
        indexes = [idx] + list(indexes)
    yield from _check_metric_labels_in(idx, indexes)


# ------------------------------------------- rule: mesh-scoped-metric-label

#: metric-name families whose cells describe a PLACEMENT, not just an
#: instance (ISSUE 17): the same engine id serving on two different
#: meshes is two different programs, so the binding must carry
#: ``mesh=<shape>`` next to its instance label or the cells blend
#: across topologies the same way unlabeled cells blend across engines.
MESH_SCOPED_FAMILIES = ("serving.engine.tp",)


@rule("mesh-scoped-metric-label",
      "topology-dependent serving cells must bind mesh=<shape> next to "
      "their instance label")
def _check_mesh_labels(idx: ModuleIndex):
    try:
        indexes = package_index() if os.path.exists(idx.path) else [idx]
    except Exception:
        indexes = [idx]
    if idx not in indexes:
        indexes = [idx] + list(indexes)
    for call, name, assigned, chained in _metric_decls(idx):
        if not name.startswith(MESH_SCOPED_FAMILIES):
            continue
        sites = []
        if chained is not None:
            attr, chain_call = chained
            if attr in _READ_METHODS:
                continue   # read-side lookup, creates no cell
            if attr in _WRITE_METHODS:
                sites = [chain_call]
        elif assigned is not None:
            sites = [s for _i, s in
                     _instance_binding_sites(indexes, assigned)]
        ok = [s for s in sites if _has_instance_kw(s)
              and any(kw.arg == "mesh" for kw in s.keywords)]
        if not ok:
            yield Finding(
                "mesh-scoped-metric-label", idx.rel, call.lineno,
                f"mesh-scoped metric {name!r} must be bound with BOTH an "
                f"instance label ({'/'.join(INSTANCE_LABEL_KEYS)}) and a "
                "mesh= label — a TP engine's cells otherwise blend across "
                "topologies")


# ------------------------------------------- rule: pool-scoped-metric-label

#: metric-name families whose cells describe a ROLE in a disaggregated
#: serving topology (ISSUE 18): a prefill replica and a decode replica
#: run the same engine/batcher code, and one scrape collects both
#: processes — a ``serving.*`` cell bound without ``pool=`` blends the
#: prefill pool's page churn into the decode pool's residency numbers,
#: which is exactly the signal the disagg router routes on.
POOL_SCOPED_FAMILIES = ("serving.",)


@rule("pool-scoped-metric-label",
      "serving cells must bind pool=<role> next to their instance label")
def _check_pool_labels(idx: ModuleIndex):
    try:
        indexes = package_index() if os.path.exists(idx.path) else [idx]
    except Exception:
        indexes = [idx]
    if idx not in indexes:
        indexes = [idx] + list(indexes)
    for call, name, assigned, chained in _metric_decls(idx):
        if not name.startswith(POOL_SCOPED_FAMILIES):
            continue
        sites = []
        if chained is not None:
            attr, chain_call = chained
            if attr in _READ_METHODS:
                continue   # read-side lookup, creates no cell
            if attr in _WRITE_METHODS:
                sites = [chain_call]
        elif assigned is not None:
            sites = [s for _i, s in
                     _instance_binding_sites(indexes, assigned)]
        ok = [s for s in sites if _has_instance_kw(s)
              and any(kw.arg == "pool" for kw in s.keywords)]
        if not ok:
            yield Finding(
                "pool-scoped-metric-label", idx.rel, call.lineno,
                f"pool-scoped metric {name!r} must be bound with BOTH an "
                f"instance label ({'/'.join(INSTANCE_LABEL_KEYS)}) and a "
                "pool= label — a disaggregated prefill/decode pair "
                "otherwise blends both roles into one cell")


# ---------------------------------------------- rule: fleet-version-label

#: metric-name families whose cells describe one VERSION of a servable
#: (ISSUE 20): the fleet runs N versions of one model concurrently
#: (incumbent + canary, or mid-swap overlap), and a cell bound without
#: ``version=`` blends two versions' latency into one p99 — which is
#: exactly the signal the canary gate promotes/rolls back on.
VERSION_SCOPED_FAMILIES = ("serving.fleet.",)
#: fleet-managed modules: ANY ``serving.*`` cell recorded here describes
#: a versioned servable, whatever its family, so the version= obligation
#: extends to the whole serving namespace inside them.
FLEET_MODULES = ("serving/fleet.py",)


@rule("fleet-version-label",
      "fleet-managed serving cells must bind version=<v> next to their "
      "engine=/pi=/model=/pool= labels")
def _check_fleet_version_labels(idx: ModuleIndex):
    try:
        indexes = package_index() if os.path.exists(idx.path) else [idx]
    except Exception:
        indexes = [idx]
    if idx not in indexes:
        indexes = [idx] + list(indexes)
    fleet_module = idx.rel in FLEET_MODULES
    for call, name, assigned, chained in _metric_decls(idx):
        if not (name.startswith(VERSION_SCOPED_FAMILIES)
                or (fleet_module and name.startswith("serving."))):
            continue
        sites = []
        if chained is not None:
            attr, chain_call = chained
            if attr in _READ_METHODS:
                continue   # read-side lookup, creates no cell
            if attr in _WRITE_METHODS:
                sites = [chain_call]
        elif assigned is not None:
            sites = [s for _i, s in
                     _instance_binding_sites(indexes, assigned)]
        bad = [s for s in sites
               if not any(kw.arg == "version" for kw in s.keywords)]
        if bad or not sites:
            yield Finding(
                "fleet-version-label", idx.rel,
                (bad[0].lineno if bad else call.lineno),
                f"fleet-managed metric {name!r} must be bound with a "
                "version= label beside its instance/pool labels — two "
                "versions of one model otherwise blend into one cell, "
                "corrupting the very p99/error deltas the canary gate "
                "decides on")


# -------------------------------------------- rule: registry-lock-discipline


def _locked_ranges(fn) -> List[Tuple[int, int]]:
    out = []
    for node in ast.walk(fn):
        if isinstance(node, ast.With):
            ctx = " ".join(_unparse(item.context_expr)
                           for item in node.items)
            if ".locked()" in ctx or "_lock" in ctx.replace(" ", ""):
                out.append((node.lineno,
                            getattr(node, "end_lineno", node.lineno)))
    return out


def _in_ranges(line: int, ranges: List[Tuple[int, int]]) -> bool:
    return any(a <= line <= b for a, b in ranges)


@rule("registry-lock-discipline",
      "read-modify-write of a registry cell (set(value()...), "
      "zero-then-inc, cross-kind shims) must run under registry.locked()")
def _check_lock_discipline(idx: ModuleIndex):
    for qual, fn in _function_scopes(idx.tree):
        ranges = _locked_ranges(fn)
        zero_bases: Dict[str, int] = {}
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or \
                    not isinstance(node.func, ast.Attribute):
                continue
            base = _unparse(node.func.value)
            attr = node.func.attr
            if attr == "set":
                # a set() whose arguments READ a cell back is an RMW
                reads = any(
                    isinstance(n, ast.Call) and
                    isinstance(n.func, ast.Attribute) and
                    n.func.attr == "value"
                    for a in node.args for n in ast.walk(a)) or any(
                    isinstance(n, ast.Subscript) and
                    "snapshot()" in _unparse(n.value)
                    for a in node.args for n in ast.walk(a))
                if reads and not _in_ranges(node.lineno, ranges):
                    yield Finding(
                        "registry-lock-discipline", idx.rel, node.lineno,
                        f"{qual}(): read-modify-write "
                        f"{base}.set(...{base}.value()...) outside "
                        "registry.locked() — concurrent writers lose "
                        "updates")
            elif attr == "zero":
                zero_bases[base] = node.lineno
            elif attr == "inc" and base in zero_bases:
                ln = zero_bases.pop(base)
                if not (_in_ranges(ln, ranges) and
                        _in_ranges(node.lineno, ranges)):
                    yield Finding(
                        "registry-lock-discipline", idx.rel, ln,
                        f"{qual}(): {base}.zero() then {base}.inc() "
                        "outside one registry.locked() block — a reader "
                        "sees the transient zero")


# ----------------------------------------------- rule: host-sync-in-hot-path

#: the per-rule site map: (path suffix, function name) pairs naming the
#: latency-critical loops. Step OUTPUTS synced here stall the async
#: dispatch pipeline; inputs (np->device conversion) are fine.
HOT_PATHS = (
    # both engines' loops (nn/engine.py): a sync there stalls every step,
    # or in fit_on_device every epoch launch
    ("nn/engine.py", "fit"),
    ("nn/engine.py", "fit_on_device"),
    ("parallel/data_parallel.py", "fit"),
    ("serving/batcher.py", "_dispatcher"),
    ("serving/batcher.py", "_run"),
    ("serving/batcher.py", "_run_engine"),
    # ISSUE 19: the continuous-batching decode loop — a host sync on a
    # decode dispatch's outputs here re-serializes the double-buffered
    # horizon pipeline (HorizonResult.fetch() is the ONE sanctioned
    # readback and is deliberately not a step callable)
    ("serving/batcher.py", "_decode_iter"),
    ("serving/batcher.py", "_emit_token"),
    ("serving/batcher.py", "_dispatch_horizon"),
    ("serving/batcher.py", "_consume_horizon"),
)

#: callables whose results are compiled-step outputs (device arrays the
#: hot loop must not sync on)
STEP_CALLABLES = ("_train_step", "step_fn", "_epoch_fn", "_run_engine",
                  "_call_engine", "decode", "decode_multi",
                  "pdecode_multi")

_SYNC_CALLS = ("float", "int")
_SYNC_NP = ("asarray", "array")


def _hot_functions(idx: ModuleIndex):
    for suffix, fname in HOT_PATHS:
        if idx.rel.endswith(suffix):
            for qual, fn in _function_scopes(idx.tree):
                if fn.name == fname:
                    yield qual, fn


def _tracked_step_outputs(fn) -> set:
    """Names/attribute paths assigned from a step-callable's result."""
    tracked = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign):
            continue
        call = node.value
        if not isinstance(call, ast.Call):
            continue
        src = _unparse(call.func)
        if not any(src == c or src.endswith("." + c) or
                   src.endswith(c) for c in STEP_CALLABLES):
            continue
        for t in node.targets:
            elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
            for e in elts:
                tracked.add(_unparse(e))
    # second-order: x = tracked_name  /  outs = out if ... else [out]
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            rhs_names = {_unparse(n) for n in ast.walk(node.value)
                         if isinstance(n, (ast.Name, ast.Attribute))}
            if rhs_names & tracked:
                tracked.add(_unparse(node.targets[0]))
    return tracked


@rule("host-sync-in-hot-path",
      "float()/.item()/np.asarray() on step outputs inside the fit-loop/"
      "dispatcher hot paths (HOT_PATHS site map) blocks async dispatch")
def _check_host_sync(idx: ModuleIndex):
    for qual, fn in _hot_functions(idx):
        tracked = _tracked_step_outputs(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            # .item()/.block_until_ready() are device syncs wherever
            # they appear in a hot path
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("item", "block_until_ready"):
                yield Finding(
                    "host-sync-in-hot-path", idx.rel, node.lineno,
                    f"{qual}(): .{node.func.attr}() in a hot path blocks "
                    "on the device — keep step outputs lazy (sync at the "
                    "listener/score read instead)")
                continue
            if not node.args:
                continue
            arg = _unparse(node.args[0])
            arg_root = arg.split("[")[0].split(".")[0]
            hit = any(arg == t or arg.startswith(t + "[") or
                      arg_root == t or arg == t.split(".")[-1]
                      for t in tracked) or arg in tracked
            if not hit:
                continue
            fname = _unparse(node.func)
            if (isinstance(node.func, ast.Name) and
                    node.func.id in _SYNC_CALLS) or \
                    fname in ("np." + a for a in _SYNC_NP) or \
                    fname in ("numpy." + a for a in _SYNC_NP):
                yield Finding(
                    "host-sync-in-hot-path", idx.rel, node.lineno,
                    f"{qual}(): {fname}({arg}) syncs a step output on "
                    "the host inside a hot path — the async dispatch "
                    "pipeline stalls every iteration")


# ------------------------------------------ rule: nondeterminism-in-compiled

#: builder functions whose bodies (including nested step fns) become
#: compiled programs — host time/randomness baked in here is a silent
#: SPMD divergence or a retrace-per-step
BUILDER_FUNCS = ("_build_train_step", "_build_epoch_fn", "_build_loss_fn",
                 "_lower_bucket", "_make_fit_step", "_fit_loss_fn",
                 "_build", "_lower_step",
                 # nn/trainstep.py, the step bodies the first two wrap
                 "gradient_tail", "engine_step", "build_epoch")

_TIME_ATTRS = ("time", "time_ns", "perf_counter", "monotonic")


@rule("nondeterminism-in-compiled",
      "time.*/random.*/np.random reachable from the train-step/engine "
      "builders would bake host state into a compiled program")
def _check_nondeterminism(idx: ModuleIndex):
    for qual, fn in _function_scopes(idx.tree):
        if fn.name not in BUILDER_FUNCS:
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Attribute):
                continue
            src = _unparse(node)
            bad = None
            if isinstance(node.value, ast.Name):
                if node.value.id == "time" and node.attr in _TIME_ATTRS:
                    bad = src
                elif node.value.id == "random":  # python stdlib random
                    bad = src
                elif node.value.id == "datetime" and node.attr in (
                        "now", "utcnow", "today"):
                    bad = src
            if bad is None and (src.startswith("np.random.") or
                                src.startswith("numpy.random.")):
                bad = src
            if bad is not None:
                yield Finding(
                    "nondeterminism-in-compiled", idx.rel, node.lineno,
                    f"{qual}(): {bad} inside a compiled-program builder — "
                    "host state baked at trace time diverges across "
                    "retraces/SPMD replicas (thread jax.random keys "
                    "instead)")


# ------------------------------------------- rule: fault-site-registration


@rule("fault-site-registration",
      "literal sites handed to faults.trip()/inject()/clear() must be in "
      "faults.SITES")
def _check_fault_sites(idx: ModuleIndex):
    from . import faults as _faults
    for node in ast.walk(idx.tree):
        if not isinstance(node, ast.Call) or \
                _call_name(node) not in ("trip", "inject", "clear"):
            continue
        site = _first_literal_arg(node)
        if site is None:
            site = _kw_literal(node, "site")
        if isinstance(site, str) and "." in site and \
                site not in _faults.SITES:
            yield Finding(
                "fault-site-registration", idx.rel, node.lineno,
                f"fault site {site!r} is not registered in faults.SITES "
                "— trip() raises at runtime, but only on the failure "
                "path nobody runs")


# --------------------------------------------------- collectors (migrated)
# The grep-the-AST collectors from tests/test_static_telemetry.py (ISSUE
# 13), now running over the cached package index so the zz coverage
# floor's cross-check shares the lint gate's single walk.


def collect_metric_names() -> Dict[str, List[str]]:
    """{relative_path: sorted([literal metric names])} for every literal
    first argument of a ``counter``/``gauge``/``histogram`` call in the
    package. Dotted names only — the registry's ``subsystem.name``
    convention — so locals/test helpers don't false-positive."""
    out = {}
    for idx in package_index():
        names = set()
        for node in ast.walk(idx.tree):
            if not isinstance(node, ast.Call) or _call_name(node) not in (
                    "counter", "gauge", "histogram"):
                continue
            name = _first_literal_arg(node)
            if isinstance(name, str) and "." in name:
                names.add(name)
        if names:
            out[idx.rel] = sorted(names)
    return out


def collect_record_compile_causes() -> List[Tuple[str, int, Optional[str]]]:
    """[(relative_path, lineno, cause_literal_or_None)] for every
    ``record_compile(...)`` call site in the package (None = the cause is
    computed, e.g. the caches' ``_consume_retrace_cause`` path)."""
    sites = []
    for idx in package_index():
        for node in ast.walk(idx.tree):
            if not isinstance(node, ast.Call) or \
                    _call_name(node) != "record_compile":
                continue
            cause = None
            if len(node.args) >= 2 and isinstance(node.args[1],
                                                  ast.Constant):
                cause = node.args[1].value
            else:
                cause = _kw_literal(node, "cause")
            sites.append((idx.rel, node.lineno, cause))
    return sites


def collect_invalidate_causes() -> List[Tuple[str, int, str]]:
    """Literal ``cause=`` kwargs on ``invalidate``/``_invalidate_compiled``
    calls — these flow verbatim into record_compile events later."""
    out = []
    for idx in package_index():
        for node in ast.walk(idx.tree):
            if not isinstance(node, ast.Call) or _call_name(node) not in (
                    "invalidate", "_invalidate_compiled"):
                continue
            cause = _kw_literal(node, "cause")
            if cause is not None:
                out.append((idx.rel, node.lineno, cause))
    return out


# ------------------------------------------------------ baseline + runner

BASELINE_FILE = "staticcheck_baseline.json"


def default_baseline_path() -> str:
    return os.path.join(repo_root(), BASELINE_FILE)


def load_baseline(path: Optional[str] = None) -> List[dict]:
    """Baseline entries: {"rule", "path", "match", "reason"} — a finding
    is grandfathered when rule+path match exactly and ``match`` is a
    substring of its message (line numbers drift; messages don't).
    Every entry MUST carry a non-empty reason (ValueError otherwise)."""
    path = path or default_baseline_path()
    if not os.path.exists(path):
        return []
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    entries = data.get("entries", data) if isinstance(data, dict) else data
    for e in entries:
        if not str(e.get("reason", "")).strip():
            raise ValueError(
                f"baseline entry without a reason: {e!r} — every "
                "grandfathered finding must say why it is acceptable")
        if not e.get("rule") or not e.get("path"):
            raise ValueError(f"malformed baseline entry: {e!r}")
    return entries


def _baseline_match(finding: Finding, entry: dict) -> bool:
    return (entry["rule"] == finding.rule and
            entry["path"] == finding.path and
            str(entry.get("match", "")) in finding.message)


@dataclasses.dataclass
class Report:
    findings: List[Finding]                 # open (gate-tripping)
    baselined: List[Tuple[Finding, dict]]
    suppressed: List[Tuple[Finding, str]]   # (finding, reason)
    stale_baseline: List[dict]
    rules: List[str]

    @property
    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out

    def as_dict(self) -> dict:
        return {
            "version": 1,
            "rules": self.rules,
            "findings": [f.as_dict() for f in self.findings],
            "baselined": [{**f.as_dict(), "reason": e["reason"]}
                          for f, e in self.baselined],
            "suppressed": [{**f.as_dict(), "reason": r}
                           for f, r in self.suppressed],
            "stale_baseline": self.stale_baseline,
            "counts": self.counts,
        }


def check_module(idx: ModuleIndex,
                 rules: Optional[Sequence[str]] = None) -> List[Finding]:
    """Raw findings for one module (suppressions applied, baseline NOT).
    A suppression without a reason surfaces as a ``bad-suppression``
    finding at the suppressing line."""
    active = [RULES[r] for r in (rules or sorted(RULES))]
    raw: List[Finding] = []
    for r in active:
        raw.extend(r.check(idx))
    out: List[Finding] = []
    for f in raw:
        sup = idx.suppression_for(f)
        if sup is None:
            out.append(f)
            continue
        ln, _rules, reason = sup
        if not (reason and reason.strip()):
            out.append(Finding(
                "bad-suppression", idx.rel, ln,
                f"suppression of {f.rule!r} has no reason — write "
                "'# staticcheck: disable=<rule> -- <why this is ok>'"))
        else:
            out.append(("suppressed", f, reason))  # type: ignore
    return out


def run(paths: Optional[Sequence[str]] = None,
        rules: Optional[Sequence[str]] = None,
        baseline_path: Optional[str] = None,
        sources: Optional[Dict[str, str]] = None) -> Report:
    """Run Tier A over the package (or explicit ``paths`` /
    ``sources={rel: source_str}`` for tests), apply suppressions and the
    baseline, and count findings into ``staticcheck.findings{rule=}``."""
    if sources is not None:
        indexes = [index_source(src, rel) for rel, src in sources.items()]
    elif paths is not None:
        indexes = [index_file(p) for p in paths]
    else:
        indexes = package_index()
    entries = load_baseline(baseline_path)
    open_findings: List[Finding] = []
    baselined: List[Tuple[Finding, dict]] = []
    suppressed: List[Tuple[Finding, str]] = []
    hit_entries: set = set()
    for idx in indexes:
        for item in check_module(idx, rules):
            if isinstance(item, tuple) and item[0] == "suppressed":
                suppressed.append((item[1], item[2]))
                continue
            f = item
            match = next((i for i, e in enumerate(entries)
                          if _baseline_match(f, e)), None)
            if match is not None:
                hit_entries.add(match)
                baselined.append((f, entries[match]))
            else:
                open_findings.append(f)
    stale = [e for i, e in enumerate(entries) if i not in hit_entries]
    rep = Report(open_findings, baselined, suppressed, stale,
                 rules=sorted(rules or RULES))
    _M_RUNS.inc()
    for f in open_findings:
        _M_FINDINGS.inc(rule=f.rule, state="open")
    for f, _e in baselined:
        _M_FINDINGS.inc(rule=f.rule, state="baselined")
    return rep


def check_source(source: str, rel: str = "<fixture>",
                 rules: Optional[Sequence[str]] = None) -> List[Finding]:
    """Tier A findings for one source string (fixture entry point —
    suppressions applied, no baseline, no telemetry)."""
    out = []
    for item in check_module(index_source(source, rel), rules):
        if isinstance(item, tuple):
            continue  # suppressed with reason
        out.append(item)
    return out


# ===========================================================================
# Tier B — compiled-program (jaxpr) audit
# ===========================================================================

JAXPR_RULES = ("no-param-cast-in-scan", "no-host-callback",
               "no-f32-leak-under-bf16-policy", "donation-applied")

# Opt-in rule (ISSUE 19): only checked when the caller declares the
# program IS a multi-token decode horizon (``expect_decode_loop=True``
# / the CLI decode probe). A horizon that silently degrades — a host
# callback smuggled into the scan body, or the scan not lowering at
# all — is numerically right but pays the per-token host round-trip
# the horizon exists to eliminate.
DECODE_RULES = ("no-host-callback-in-decode",)

# Opt-in rules (ISSUE 16): only checked when the caller declares the
# program SHOULD be fused (``expect_fusion=True`` / the CLI fusion
# probe). A dispatcher that silently falls back leaves the program
# numerically right but slow — exactly the failure mode runtime tests
# can't see, so the lint gate traces the real step and inspects it.
FUSION_RULES = ("fusion-applied-epilogue", "fusion-applied-updater")

_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "outside_call", "host_callback", "callback")
_LOOP_PRIMS = ("scan", "while")
_16BIT = ("bfloat16", "float16")


def _walk_jaxpr(jaxpr, visit, inside_loop=False):
    for eqn in jaxpr.eqns:
        visit(eqn, inside_loop)
        inner_loop = inside_loop or eqn.primitive.name in _LOOP_PRIMS
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else (v,)
            for vv in vals:
                inner = getattr(vv, "jaxpr", None)
                if inner is not None:
                    _walk_jaxpr(inner, visit, inner_loop)


def jaxpr_audit(fn, args=(), rules: Optional[Sequence[str]] = None, *,
                param_shapes: Sequence[Tuple[int, ...]] = (),
                policy: Optional[str] = None,
                expect_donation: bool = False,
                expect_fusion: bool = False,
                expect_decode_loop: bool = False,
                lowered_text: Optional[str] = None,
                label: str = "<fn>") -> List[Finding]:
    """Audit a compiled program's jaxpr against the Tier B rules — the
    generalization of the r12/r18 one-off regressions. ``fn`` is a
    jitted function (``__wrapped__`` is unwrapped automatically) traced
    with ``args`` (avals work; nothing executes).

    - ``no-param-cast-in-scan``: no 16-bit ``convert_element_type``
      whose output shape matches a ``param_shapes`` entry inside a
      scan/while body (the per-microbatch master cast the r12 hoist
      removed must never leak back).
    - ``no-host-callback``: no callback/outside_call primitives — a
      host round-trip per step hides in an innocuous-looking print.
    - ``no-f32-leak-under-bf16-policy``: under a 16-bit ``policy``,
      every dot_general/conv contracts 16-bit operands (f32 operands
      mean a cast was dropped and the MXU runs at half rate).
    - ``donation-applied``: the lowered program carries input/output
      aliasing (``expect_donation=True`` + ``lowered_text``) — donation
      silently not applying doubles peak HBM.
    - ``fusion-applied-epilogue`` (``expect_fusion=True`` only): the
      program contains at least one ``pallas_call`` — a build that
      claims epilogue fusion but lowered zero kernels silently fell
      back to the standalone BN-then-activation chain.
    - ``fusion-applied-updater`` (``expect_fusion=True`` only): no
      top-level f32->16-bit ``convert_element_type`` reads a program
      INPUT with a ndim>=2 ``param_shapes`` shape — that is the
      standalone master cast-sweep at the head of the step; the fused
      updater casts only the freshly-updated masters (intermediates).
    - ``no-host-callback-in-decode`` (``expect_decode_loop=True``
      only, ISSUE 19): the multi-token decode horizon contains zero
      host-callback primitives, lowers an actual ``scan``/``while``
      loop, and performs exactly ONE logits->token ``argmax`` reduction
      per scan iteration — a silently-degraded horizon fails the lint
      build instead of quietly paying per-token host round-trips.
    """
    import jax
    rules = tuple(rules or JAXPR_RULES)
    if expect_fusion:
        rules = rules + tuple(r for r in FUSION_RULES if r not in rules)
    if expect_decode_loop:
        rules = rules + tuple(r for r in DECODE_RULES if r not in rules)
    findings: List[Finding] = []
    target = getattr(fn, "__wrapped__", fn)
    closed = jax.make_jaxpr(target)(*args)
    pshapes = {tuple(s) for s in param_shapes}
    mixed16 = False
    if policy is not None:
        from .. import dtypes as _dt
        try:
            mixed16 = str(_dt.resolve(policy)) in _16BIT
        except Exception:
            mixed16 = str(policy).lower() in ("bfloat16", "float16",
                                              "bf16", "f16", "half")

    top_invars = set(id(v) for v in closed.jaxpr.invars)
    pallas_calls = [0]
    loops = [0]
    argmax_in_loop = [0]

    def visit(eqn, inside_loop):
        name = eqn.primitive.name
        if "pallas_call" in name:
            pallas_calls[0] += 1
        if name in _LOOP_PRIMS:
            loops[0] += 1
        if name == "argmax" and inside_loop:
            argmax_in_loop[0] += 1
        if "no-host-callback-in-decode" in rules and any(
                c in name for c in _CALLBACK_PRIMS):
            findings.append(Finding(
                "no-host-callback-in-decode", label, 0,
                f"host callback primitive {name!r} inside the compiled "
                "decode horizon — the k-token loop round-trips to the "
                "host it exists to bypass"))
        if "fusion-applied-updater" in rules and \
                name == "convert_element_type" and pshapes:
            iv, ov = eqn.invars[0], eqn.outvars[0]
            if (id(iv) in top_invars
                    and str(getattr(iv, "aval", ov.aval).dtype) == "float32"
                    and str(ov.aval.dtype) in _16BIT
                    and len(ov.aval.shape) >= 2
                    and tuple(ov.aval.shape) in pshapes):
                findings.append(Finding(
                    "fusion-applied-updater", label, 0,
                    f"param-shaped f32->{ov.aval.dtype} cast "
                    f"{tuple(ov.aval.shape)} reads a program input — the "
                    "standalone master cast-sweep still heads the step; "
                    "the fused updater was expected to fold it into the "
                    "updater write (silent fallback?)"))
        if "no-host-callback" in rules and any(
                c in name for c in _CALLBACK_PRIMS):
            findings.append(Finding(
                "no-host-callback", label, 0,
                f"host callback primitive {name!r} in the compiled "
                "program — every step round-trips to the host"))
        if "no-param-cast-in-scan" in rules and inside_loop and \
                name == "convert_element_type" and pshapes:
            ov = eqn.outvars[0]
            if str(ov.aval.dtype) in _16BIT and \
                    tuple(ov.aval.shape) in pshapes:
                findings.append(Finding(
                    "no-param-cast-in-scan", label, 0,
                    f"param-shaped {ov.aval.dtype} cast "
                    f"{tuple(ov.aval.shape)} inside a scan body — the "
                    "master->compute cast re-materializes every "
                    "microbatch (hoist it out of the scan, r12)"))
        if "no-f32-leak-under-bf16-policy" in rules and mixed16 and \
                name in ("dot_general", "conv_general_dilated"):
            dts = [str(v.aval.dtype) for v in eqn.invars]
            if any(d == "float32" for d in dts):
                findings.append(Finding(
                    "no-f32-leak-under-bf16-policy", label, 0,
                    f"{name} contracts float32 operands {dts} under a "
                    "16-bit compute policy — a cast was dropped and the "
                    "MXU runs at half rate"))

    _walk_jaxpr(closed.jaxpr, visit)
    if "no-host-callback-in-decode" in rules:
        if loops[0] == 0:
            findings.append(Finding(
                "no-host-callback-in-decode", label, 0,
                "no scan/while loop in the multi-token decode program — "
                "the horizon silently degraded to straight-line code "
                "(unrolled or collapsed); the per-(cache x horizon) "
                "bucket compile strategy assumes ONE compiled loop"))
        elif argmax_in_loop[0] != 1:
            findings.append(Finding(
                "no-host-callback-in-decode", label, 0,
                f"{argmax_in_loop[0]} logits->token argmax reductions "
                "inside the decode scan body (expected exactly 1 per "
                "iteration) — sampling is duplicated or was hoisted out "
                "of the compiled loop"))
    if "fusion-applied-epilogue" in rules and pallas_calls[0] == 0:
        findings.append(Finding(
            "fusion-applied-epilogue", label, 0,
            "expect_fusion but the compiled program contains zero "
            "pallas_call kernels — the epilogue dispatcher silently fell "
            "back to the standalone normalization/activation chain"))
    if "donation-applied" in rules and expect_donation:
        if lowered_text is None and hasattr(fn, "lower"):
            try:
                lowered_text = fn.lower(*args).as_text()
            except Exception:
                lowered_text = None
        if lowered_text is not None and \
                "tf.aliasing_output" not in lowered_text:
            findings.append(Finding(
                "donation-applied", label, 0,
                "donate_argnums declared but the lowered program carries "
                "no input/output aliasing — donation silently not "
                "applied doubles peak HBM"))
    return findings


def audit_model(model, batch_size: int, accum_steps: int = 1,
                seq_len: Optional[int] = None,
                rules: Optional[Sequence[str]] = None) -> List[Finding]:
    """Tier B audit of ``model``'s REAL fused train step at
    ``batch_size`` (the program ``fit()`` runs — sentinel, remat policy,
    accum scan and all). Nothing executes: the step is traced/lowered on
    avals only. Returns ``[]`` when the program is clean."""
    import jax
    import numpy as np
    from ..nn import memory as _mem
    from . import sentinel as _sent
    if not model.params and not model.state:
        model.init()
    x, y = _mem._batch_avals(model, batch_size, seq_len)
    pa = jax.eval_shape(lambda: model.params)
    oa = jax.eval_shape(lambda: model.updater_state)
    sa = jax.eval_shape(lambda: model.state)
    step_aval = jax.ShapeDtypeStruct((), np.int32)
    key_aval = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    fm = (None,) * len(x) if isinstance(x, tuple) else None
    lm = (None,) * len(y) if isinstance(y, tuple) else None
    step = model._build_train_step(accum_steps)
    label = f"<{type(model).__name__}.train_step batch={batch_size}>"
    lowered_text = None
    if "donation-applied" in (rules or JAXPR_RULES):
        lowered_text = step.lower(
            pa, oa, sa, step_aval, key_aval, x, y, fm, lm,
            _sent.counter_avals()).as_text()
    return jaxpr_audit(
        step, (pa, oa, sa, step_aval, key_aval, x, y, fm, lm),
        rules,
        param_shapes=[tuple(l.shape) for l in jax.tree.leaves(model.params)],
        policy=str(getattr(model.conf, "dtype", "FLOAT")),
        expect_donation=True, lowered_text=lowered_text, label=label)


def fusion_probe() -> List[Finding]:
    """Trace a tiny bf16 conv->BN->relu model's FUSED train step under
    ``DL4J_TPU_FUSED_EPILOGUES=force`` and assert the fusion actually
    lowered (ISSUE 16): at least one ``pallas_call`` in the program and
    no standalone master cast-sweep reading the step's inputs. Runs from
    the CLI so ``make lint`` fails on a silent dispatcher fallback —
    the one regression runtime parity tests cannot catch, because the
    fallback is bit-identical and only slow. Nothing executes (aval
    trace only); force mode is restored afterwards."""
    import jax
    import numpy as np
    from .. import dtypes as _dt
    from ..nn.config import InputType, NeuralNetConfiguration
    from ..nn.layers.conv import BatchNormalization, ConvolutionLayer
    from ..nn.layers.core import ActivationLayer, OutputLayer
    from ..nn.model import MultiLayerNetwork
    from ..nn.updaters import Sgd
    from ..ops import fused_epilogues as _fe

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Sgd(learning_rate=0.05))
            .data_type("BFLOAT16")
            .input_type(InputType.convolutional(3, 8, 8,
                                                data_format="NHWC"))
            .list(ConvolutionLayer(n_out=8, kernel=(3, 3), mode="same",
                                   activation="identity",
                                   data_format="NHWC"),
                  BatchNormalization(data_format="NHWC"),
                  ActivationLayer(activation="relu"),
                  OutputLayer(n_out=3)).build())
    model = MultiLayerNetwork(conf).init()
    label = "<fusion_probe bf16 conv/BN/relu batch=4>"
    prev = _fe.set_mode("force")
    try:
        if not model.fused_updater_active():
            return [Finding(
                "fusion-applied-updater", label, 0,
                "fused master-cast updater inactive for a plain bf16 "
                "Sgd model — route_updater rejected the canonical case")]
        step = model._build_train_step(fused_cast=True)
        cdt = _dt.resolve(conf.dtype)
        pa = jax.eval_shape(lambda: model.params)
        pca = jax.eval_shape(lambda: _dt.cast_floating(model.params, cdt))
        oa = jax.eval_shape(lambda: model.updater_state)
        sa = jax.eval_shape(lambda: model.state)
        step_aval = jax.ShapeDtypeStruct((), np.int32)
        key_aval = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        x = jax.ShapeDtypeStruct((4, 8, 8, 3), np.float32)
        y = jax.ShapeDtypeStruct((4, 3), np.float32)
        return jaxpr_audit(
            step, (pa, pca, oa, sa, step_aval, key_aval, x, y, None, None),
            rules=(), expect_fusion=True,
            param_shapes=[tuple(l.shape)
                          for l in jax.tree.leaves(model.params)],
            policy=str(conf.dtype), label=label)
    finally:
        _fe.set_mode(prev)


def decode_probe() -> List[Finding]:
    """Trace a tiny generative engine's k-token decode horizon program
    and audit it with ``no-host-callback-in-decode`` (ISSUE 19): zero
    host callbacks, a real compiled loop, exactly one logits->token
    reduction per scan iteration. Runs from the CLI so ``make lint``
    fails on a silently-degraded horizon — like the fusion probe, this
    is the one regression parity tests cannot catch, because a
    degraded horizon is bit-identical and only slow. Nothing executes
    (aval trace only)."""
    from ..nn.config import InputType, NeuralNetConfiguration
    from ..nn.layers.attention import SelfAttentionLayer
    from ..nn.layers.core import DenseLayer, OutputLayer
    from ..nn.model import MultiLayerNetwork
    from ..serving.engine import GenerativeEngine

    V = 8
    conf = (NeuralNetConfiguration.builder().seed(3)
            .input_type(InputType.recurrent(V, 4))
            .list(SelfAttentionLayer(n_out=V, n_heads=2),
                  DenseLayer(n_out=16, activation="relu"),
                  OutputLayer(n_out=V, activation="softmax"))
            .build())
    model = MultiLayerNetwork(conf).init()
    eng = GenerativeEngine(model, slots=2)
    fn, avals = eng.decode_multi_traceable(16, 4)
    return jaxpr_audit(fn, avals, rules=(), expect_decode_loop=True,
                       label="<decode_probe greedy horizon k=4>")


# ------------------------------------------------------------------- CLI


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m deeplearning4j_tpu.runtime.staticcheck",
        description="JAX-aware lint over the deeplearning4j_tpu package")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--baseline", default=None,
                   help=f"baseline JSON (default: {BASELINE_FILE} at the "
                        "repo root)")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule subset (default: all)")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--no-fusion-probe", action="store_true",
                   help="skip the Tier B fused-train-step trace (ISSUE "
                        "16); the AST rules still run")
    p.add_argument("--emit-baseline", action="store_true",
                   help="print baseline-entry skeletons for the open "
                        "findings (add a reason to each before checking "
                        "them in)")
    args = p.parse_args(argv)
    if args.list_rules:
        for name in sorted(RULES):
            print(f"{name}: {RULES[name].help}")
        return 0
    rules = [r.strip() for r in args.rules.split(",")] if args.rules \
        else None
    if rules:
        unknown = [r for r in rules if r not in RULES]
        if unknown:
            print(f"unknown rules: {unknown} (see --list-rules)",
                  file=sys.stderr)
            return 2
    try:
        rep = run(rules=rules, baseline_path=args.baseline)
    except ValueError as e:  # malformed baseline
        print(f"staticcheck: {e}", file=sys.stderr)
        return 2
    if not args.no_fusion_probe and rules is None:
        # Tier B gate: a silent epilogue/updater fallback is invisible to
        # parity tests (bit-identical, just slow) — fail the lint build.
        rep.findings.extend(fusion_probe())
        # same failure mode for the decode horizon (ISSUE 19): a
        # degraded k-token loop is bit-identical and only slow
        rep.findings.extend(decode_probe())
    if args.emit_baseline:
        print(json.dumps({"entries": [
            {"rule": f.rule, "path": f.path,
             "match": f.message[:60], "reason": "<why is this ok?>"}
            for f in rep.findings]}, indent=1))
        return 0 if not rep.findings else 1
    if args.format == "json":
        print(json.dumps(rep.as_dict(), indent=1))
    else:
        for f in rep.findings:
            print(str(f))
        for f, e in rep.baselined:
            print(f"{f}  [baselined: {e['reason']}]")
        for e in rep.stale_baseline:
            print(f"stale baseline entry (fixed? remove it): {e}",
                  file=sys.stderr)
        n = len(rep.findings)
        print(f"staticcheck: {n} open finding(s), "
              f"{len(rep.baselined)} baselined, "
              f"{len(rep.suppressed)} suppressed, "
              f"{len(RULES)} rules active")
    return 1 if rep.findings else 0


if __name__ == "__main__":
    sys.exit(main())
