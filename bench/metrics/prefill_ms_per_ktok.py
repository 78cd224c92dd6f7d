"""Model step, prefill: device time of the prefill programs per 1000
prompt tokens prefilled in the window."""
UNIT = "ms"


def read(ctx):
    pre = (ctx.trace or {}).get("modules", {}).get("prefill")
    tokens = sum(ctx.prefill_lens)
    if not pre or not pre["device_s"] or not tokens:
        return None
    return 1e3 * pre["device_s"] / (tokens / 1e3)
