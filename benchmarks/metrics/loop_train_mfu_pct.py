"""A looped decoder's whole step as a share of the chips' peak
(``host_clock``): ``harness.flops_loop``'s count of forward + backward
operations (3 x forward over every pass and every pass's head,
recomputation not counted) times the sequences completed in the window, over
window seconds x chips x the table's bf16 peak. A configuration that walks
its layers once (no ``total_ut_steps``) leaves the metric out."""

from benchmarks.harness import flops_loop


def read(ctx):
    if ctx["peaks"] is None or "total_ut_steps" not in ctx["config"]:
        return None
    w = ctx["window"]
    done = flops_loop.train_flops_per_example(ctx["config"], ctx["traffic"]) \
        * w["examples"]
    peak = ctx["peaks"]["flops_bf16"] * ctx["chips"] * w["seconds"]
    return {"value": 100.0 * done / peak, "unit": "%"}
