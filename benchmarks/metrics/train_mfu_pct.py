"""The whole step's share of the chips' peak: the benchmark's own count of
forward + backward operations (3 x forward, recomputation not counted) times
the examples completed in the window, over window seconds x chips x the
table's bf16 peak."""

from benchmarks.harness import flops


def read(ctx):
    if ctx["peaks"] is None:
        return None
    w = ctx["window"]
    done = flops.train_flops_per_example(ctx["config"], ctx["traffic"]) \
        * w["examples"]
    peak = ctx["peaks"]["flops_bf16"] * ctx["chips"] * w["seconds"]
    return {"value": 100.0 * done / peak, "unit": "%"}
