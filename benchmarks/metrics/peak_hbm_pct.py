"""``memory_stats()["peak_bytes_in_use"]`` over ``bytes_limit`` after the
window, on the fullest device."""


def read(ctx):
    peak, limit = ctx["memory"]
    if not peak or not limit:
        return None
    return {"value": 100.0 * peak / limit, "unit": "%"}
