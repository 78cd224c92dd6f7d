"""Engine admission: time inside the engine's ``serve.admit`` spans with
the device idle (host work, dispatch and the first tokens' fetch), per
admitted request (``bench/program_trace.py``)."""
UNIT = "ms"


def read(ctx):
    admit = (ctx.trace or {}).get("admit_device")
    if not admit or not admit["prefill_spans"]:
        return None
    return 1e3 * admit["idle_s"] / admit["prefill_spans"]
