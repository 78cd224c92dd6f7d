"""Whole prefill step of a hybrid model: the operations of the prompts
prefilled in the window (``bench/roofline_hybrid.py``: the matrix work of
every layer, causal attention in the attention layers, the chunked SSD
scan in the Mamba layers), over the prefill programs' device time times
the chip's peak."""
from bench import roofline_hybrid

UNIT = "%"


def read(ctx):
    pre = (ctx.trace or {}).get("modules", {}).get("prefill")
    if not pre or not pre["device_s"] or not ctx.prefill_lens or not ctx.peak:
        return None
    flops = sum(roofline_hybrid.prefill(ctx.cfg, n)[0]
                for n in ctx.prefill_lens)
    return 100.0 * flops / (pre["device_s"] * ctx.peak["flops"])
