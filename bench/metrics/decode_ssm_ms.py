"""Decode kernels: device time per tick of the operations under the
model's ``ssm`` scope (the Mamba layers' projections, conv, recurrence
and gated norm), from the trace of the window and the tick's HLO
(``bench/program_trace.py``)."""
UNIT = "ms"


def read(ctx):
    scopes = (ctx.trace or {}).get("tick_scopes")
    if not scopes or not scopes["ticks"]:
        return None
    return 1e3 * scopes["seconds"].get("ssm", 0.0) / scopes["ticks"]
