"""Decode kernels of a hybrid model: the least time the chip could take
for the window's ticks (``bench/roofline_hybrid.py``: weights read once a
tick, every live slot's SSM and conv state read and written in each
Mamba layer, live KV of the attention layers only), over the tick's
device time.  Each tick's live slots and the sum of their positions are
the tick's own counts (``BatchedEngine.tick_stats``)."""
from bench import roofline, roofline_hybrid

UNIT = "%"


def read(ctx):
    tick = (ctx.trace or {}).get("modules", {}).get("tick")
    rows = [r for r in ctx.tick_stats if "live_positions" in r]
    if not tick or not tick["device_s"] or not rows or not ctx.peak:
        return None
    least = sum(roofline.roofline_s(
        *roofline_hybrid.decode_step(ctx.cfg, r["live_slots"],
                                     r["live_positions"]), ctx.peak)
        for r in rows)
    return 100.0 * least / tick["device_s"]
