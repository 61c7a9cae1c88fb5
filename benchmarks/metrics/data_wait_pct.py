"""Share of the window that the fit loop waited for its next batch: the sum
of the program's ``train.phase.data_wait_s`` observations in the window over
the window (``program_counter``). Entry points that keep no such clock
observe nothing, and the metric is then left out."""


def read(ctx):
    waited = ctx["data_wait_s"]
    if not waited:
        return None
    return {"value": 100.0 * waited / ctx["window"]["seconds"], "unit": "%"}
