"""A causal decoder's whole step as a share of the chips' peak
(``host_clock``): ``harness.flops_lm``'s count of forward + backward
operations (3 x forward, recomputation not counted) times the sequences
completed in the window, over window seconds x chips x the table's bf16
peak. ``train_mfu_pct``'s count sends every token configuration to BERT's
formula, so a decoder brings its own."""

from benchmarks.harness import flops_lm


def read(ctx):
    if ctx["peaks"] is None:
        return None
    w = ctx["window"]
    done = flops_lm.train_flops_per_example(ctx["config"], ctx["traffic"]) \
        * w["examples"]
    peak = ctx["peaks"]["flops_bf16"] * ctx["chips"] * w["seconds"]
    return {"value": 100.0 * done / peak, "unit": "%"}
