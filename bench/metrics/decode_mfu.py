"""Whole decode step: the model operations the live slots needed, over the
tick's device time times the chip's peak (``bench/roofline.py``)."""
UNIT = "%"


def read(ctx):
    tick = (ctx.trace or {}).get("modules", {}).get("tick")
    if not tick or not tick["device_s"] or not ctx.tick_work or not ctx.peak:
        return None
    flops = sum(f for f, _ in ctx.tick_work)
    return 100.0 * flops / (tick["device_s"] * ctx.peak["flops"])
