"""Model step, decode: device time of the jitted tick per tick, from the
trace of the window."""
UNIT = "ms"


def read(ctx):
    tick = (ctx.trace or {}).get("modules", {}).get("tick")
    if not tick or not tick["count"]:
        return None
    return 1e3 * tick["device_s"] / tick["count"]
