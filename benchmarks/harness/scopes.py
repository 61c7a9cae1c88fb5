"""The program's scopes laid over the device's operations.

A device trace names every operation by its HLO instruction (``%fusion.12 =
f32[1024]{0} fusion(...)``) and carries none of the program's scopes. The
program keeps, for the programs it dispatched, a table of every instruction
with its scope path, its phase (``forward``, ``recompute``, ``backward``,
``updater``, ``sentinel``, ``clip`` or ``other``: the program's rule, it
owns the names) and the vertex of the model the path lies under
(``telemetry.program_scopes``). :func:`attribute` joins the two by the
instruction's name and checks the first result's shape; :func:`shares` does
it once a run for the six readers (``fwd_time_pct``, ``bwd_time_pct``,
``recompute_time_pct``, ``updater_time_pct``, ``attn_time_pct``,
``scope_unattributed_pct``) and says on stderr what else the join shows. A
program without tables (the parent of the PR that brought them) gives
``None``, and every reader then leaves its metric out.
"""

from __future__ import annotations

import re
import sys
import time

from . import trace

#: phases whose shares, with the unattributed rest, add up to the busy time
PHASES = ("forward", "recompute", "backward", "updater", "sentinel", "clip")
#: the attention operation's scopes, the same thing in every decoder cell
ATTENTION = ("attn.full", "attn.window", "attn.latent")
_EVENT = re.compile(r"^%?([\w.\-]+) = \(?([a-z][a-z0-9]*\[[^\]]*\])?")


def say(msg: str) -> None:
    print(f"benchmark: scopes: {msg}", file=sys.stderr, flush=True)


def tables_of(program):
    """The program's scope tables, or None where it keeps none."""
    read = getattr(program, "program_scopes", None)
    if read is None:
        return None
    return read() or None


def attribute(reduced, tables):
    """Join the busiest device's operations inside the traced window with
    ``tables`` (``telemetry.program_scopes()``). Self times: a ``while``
    holds its body's events and counts only what they leave. An event joins
    the instruction of its name whose first result has its shape; where two
    programs have such an instruction, the one that matches more of the
    window's time; what joins nothing, or an instruction of phase ``other``,
    is unattributed. -> nanoseconds: ``busy``, ``joined`` (name and shape),
    ``name_only``, ``unjoined``, ``mixed`` (fusions that hold more than one
    phase), ``phase`` {phase: ns}, ``scope`` {path component: ns}, ``vertex``
    {vertex: {phase: ns}}, ``unattributed_ops`` {short name: ns} and
    ``unattributed_scopes`` {scope path, or why there is none: ns}; or None
    without tables."""
    if not tables:
        return None
    lo, hi = reduced.window
    dev = max(reduced.devices.values(), key=lambda d: d["busy_ns"])
    ops = [(max(s, lo), min(e, hi), n) for s, e, n in dev["ops"]
           if min(e, hi) > max(s, lo)]
    took = {}
    for n, t in trace.self_times(ops):
        took[n] = took.get(n, 0) + t

    # event -> (instruction name, a program holds the name, the programs
    # that hold it with the event's shape); and how much of the window each
    # program matches alone, for the names two of them hold with one shape
    weight = [0] * len(tables)
    joins = {}
    for event, ns in took.items():
        m = _EVENT.match(event)
        name, shape = (m.group(1), m.group(2) or "") if m else (None, "")
        held = [i for i, t in enumerate(tables) if name in t["instructions"]]
        exact = [i for i in held
                 if tables[i]["instructions"][name]["shape"] == shape]
        joins[event] = (name, bool(held), exact)
        if len(exact) == 1:
            weight[exact[0]] += ns
    out = {"busy": dev["busy_ns"], "joined": 0, "name_only": 0,
           "unjoined": 0, "mixed": 0, "phase": {}, "scope": {}, "vertex": {},
           "unattributed_ops": {}, "unattributed_scopes": {}}
    for event, ns in took.items():
        name, held, exact = joins[event]
        if not exact:
            out["name_only" if held else "unjoined"] += ns
            phase, where = "other", "(not joined)"
        else:
            out["joined"] += ns
            ins = tables[max(exact, key=lambda i: weight[i])][
                "instructions"][name]
            phase, where = ins["phase"], ins["scope"] or "(no op_name)"
            if len(ins["phases_inside"]) > 1:
                out["mixed"] += ns
            for c in set(ins["scopes"]):
                out["scope"][c] = out["scope"].get(c, 0) + ns
            if ins["vertex"] is not None:
                v = out["vertex"].setdefault(ins["vertex"], {})
                v[phase] = v.get(phase, 0) + ns
        out["phase"][phase] = out["phase"].get(phase, 0) + ns
        if phase == "other":
            for acc, key in ((out["unattributed_ops"],
                              trace.short_name(event)),
                             (out["unattributed_scopes"], where)):
                acc[key] = acc.get(key, 0) + ns
    return out


def _pct(ns, busy):
    return 100.0 * ns / busy if busy else 0.0


def _top(acc: dict, k: int = 10):
    return sorted(acc.items(), key=lambda kv: -kv[1])[:k]


def describe(att, vertices=()):
    """Lines for stderr: the phases, the share joined, the ten largest
    scopes, the ten largest vertices with their phase split (vertices that
    differ only in their digits are one line, ``l*.attn``: a decoder has
    hundreds), and what is unattributed."""
    busy = att["busy"]
    pct = lambda ns: f"{_pct(ns, busy):.2f}"
    lines = ["phases % of busy: " + ", ".join(
        f"{p} {pct(att['phase'].get(p, 0))}" for p in PHASES + ("other",))]
    lines.append(
        f"joined by name and shape {pct(att['joined'])}, by name alone "
        f"{pct(att['name_only'])}, not at all {pct(att['unjoined'])}; in "
        f"fusions of more than one phase {pct(att['mixed'])}")
    vertices = set(vertices) | set(att["vertex"])
    lines.append("scopes: " + ", ".join(
        f"{c} {pct(ns)}" for c, ns in _top(
            {c: ns for c, ns in att["scope"].items() if c not in vertices})))
    kinds = {}
    for v, by in att["vertex"].items():
        k = kinds.setdefault(re.sub(r"\d+", "*", v), {})
        for p, ns in by.items():
            k[p] = k.get(p, 0) + ns
    lines.append("vertices: " + "; ".join(
        f"{v} {pct(ns)} ("
        + " ".join(f"{p[:3]} {pct(kinds[v][p])}"
                   for p in PHASES if kinds[v].get(p)) + ")"
        for v, ns in _top({v: sum(by.values())
                           for v, by in kinds.items()})))
    lines.append("unattributed: " + ", ".join(
        f"{n} {pct(ns)}" for n, ns in _top(att["unattributed_ops"])))
    lines.append("unattributed, by scope: " + ", ".join(
        f"{n} {pct(ns)}" for n, ns in _top(att["unattributed_scopes"])))
    return lines


def shares(ctx):
    """:func:`attribute` of this run's trace, made once and kept in ``ctx``
    for the six readers; None without a trace or without tables."""
    if "scopes" not in ctx:
        ctx["scopes"] = None
        if ctx.get("trace") is not None:
            from deeplearning4j_tpu.runtime import telemetry
            t0 = time.perf_counter()
            tables = tables_of(telemetry)
            t1 = time.perf_counter()
            att = attribute(ctx["trace"], tables)
            if att is not None:
                say(f"{len(tables)} programs "
                    f"({', '.join(t['site'] for t in tables)}), "
                    f"{sum(len(t['instructions']) for t in tables)} "
                    f"instructions: text and parse {t1 - t0:.2f}s, join "
                    f"{time.perf_counter() - t1:.2f}s")
                vertices = [v for t in tables
                            for v in t["labels"].get("vertices", ())]
                for line in describe(att, vertices):
                    say(line)
            ctx["scopes"] = att
    return ctx["scopes"]


def phase_pct(ctx, phase: str):
    """One phase's share of the busiest device's busy time, or None."""
    att = shares(ctx)
    if att is None or not att["busy"]:
        return None
    return {"value": _pct(att["phase"].get(phase, 0), att["busy"]),
            "unit": "%"}
