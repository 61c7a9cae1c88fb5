"""Share of the traced window in which a collective ran on a device while no
compute operation did, on the worst device (``device_trace``). A trace with
no collective leaves the metric out."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not any(d["collective_ns"] for d in tr.devices.values()):
        return None
    worst = max(d["collective_exposed_ns"] for d in tr.devices.values())
    return {"value": 100.0 * worst / (tr.window_s * 1e9), "unit": "%"}
