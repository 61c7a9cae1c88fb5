"""How unevenly the router loads the experts held here
(``program_counter``): from the program's ``moe.tokens{layer=,expert=}``
counters, the fullest held expert's tokens over the mean of the held, for
the layer where that is largest. 1 is an even load; the step waits for the
fullest expert's rows. The counters run from the start of the process and
the harness hands a reader no snapshot, so the reading is over the first
call, the window and the traced calls together: the same batches scanned
over and over. A program that keeps no such counter leaves the metric
out."""


def read(ctx):
    if ctx.get("peaks") is None:
        return None
    from deeplearning4j_tpu.runtime import telemetry
    metric = telemetry.registry.get("moe.tokens")
    held = (ctx["config"].get("deployment") or {}).get("held")
    if metric is None or not held:
        return None
    by_layer = {}
    for key, n in metric.series().items():
        labels = dict(key)
        by_layer.setdefault(labels.get("layer"), []).append(n)
    worst = None
    for counts in by_layer.values():
        mean = sum(counts) / held[1]
        if mean > 0:
            ratio = max(counts) / mean
            worst = ratio if worst is None else max(worst, ratio)
    if worst is None:
        return None
    return {"value": worst, "unit": "ratio"}
