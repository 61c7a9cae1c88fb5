"""A Gated DeltaNet decoder's whole step as a share of the chips' peak
(``host_clock``): ``harness.flops_gdn``'s count of forward + backward
operations (3 x forward, recomputation not counted) times the sequences
completed in the window, over window seconds x chips x the table's bf16
peak. A configuration without the linear layers' keys leaves the metric
out."""

from benchmarks.harness import flops_gdn


def read(ctx):
    if ctx["peaks"] is None or "linear_num_value_heads" not in ctx["config"]:
        return None
    w = ctx["window"]
    done = flops_gdn.train_flops_per_example(ctx["config"], ctx["traffic"]) \
        * w["examples"]
    peak = ctx["peaks"]["flops_bf16"] * ctx["chips"] * w["seconds"]
    return {"value": 100.0 * done / peak, "unit": "%"}
