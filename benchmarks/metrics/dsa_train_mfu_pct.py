"""A selected-key (DeepSeek sparse attention) decoder's whole step as a share
of the chips' peak (``host_clock``): ``harness.flops_dsa``'s count of forward
+ backward operations (3 x forward, recomputation not counted, the attention's
scores over the pairs the selection leaves open and the indexer's over all
causal pairs) times the sequences completed in the window, over window
seconds x chips x the table's bf16 peak. A configuration without the
indexer's keys leaves the metric out."""

from benchmarks.harness import flops_dsa


def read(ctx):
    if ctx["peaks"] is None or "sa_config" not in ctx["config"]:
        return None
    w = ctx["window"]
    done = flops_dsa.train_flops_per_example(ctx["config"], ctx["traffic"]) \
        * w["examples"]
    peak = ctx["peaks"]["flops_bf16"] * ctx["chips"] * w["seconds"]
    return {"value": 100.0 * done / peak, "unit": "%"}
