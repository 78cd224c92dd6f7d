"""Decode step kernels: the least time the chip could take for the ticks
of the window (weights read once a tick, live KV only; bound by HBM at
these sizes), over the tick's device time."""
from bench import roofline

UNIT = "%"


def read(ctx):
    tick = (ctx.trace or {}).get("modules", {}).get("tick")
    if not tick or not tick["device_s"] or not ctx.tick_work or not ctx.peak:
        return None
    least = sum(roofline.roofline_s(f, b, ctx.peak) for f, b in ctx.tick_work)
    return 100.0 * least / tick["device_s"]
