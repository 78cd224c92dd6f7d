"""Model step, decode: device time per tick of the operations under no
model scope: the layer scan's slicing and restacking of the page pools
and XLA's own copies (``bench/program_trace.py``)."""
UNIT = "ms"


def read(ctx):
    scopes = (ctx.trace or {}).get("tick_scopes")
    if not scopes or not scopes["ticks"]:
        return None
    return 1e3 * scopes["seconds"].get("none", 0.0) / scopes["ticks"]
