"""1 - the union of the device's operation intervals over the traced window,
on the device that was busy the longest (``device_trace``)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return {"value": 100.0 * tr.idle_share_fullest(), "unit": "%"}
