"""Tick loop: live slots per decode tick in the window, as the tick itself
counts them (``BatchedEngine.tick_stats``)."""
UNIT = "slots"


def read(ctx):
    rows = ctx.tick_stats
    if not rows:
        return None
    return sum(r["live_slots"] for r in rows) / len(rows)
