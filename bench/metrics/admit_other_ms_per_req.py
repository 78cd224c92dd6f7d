"""Page pool: device time inside the engine's ``serve.admit`` spans
outside the prefill program (the page scatter into the whole pools, the
strips' pads and transposes, the first tokens), per admitted request
(``bench/program_trace.py``)."""
UNIT = "ms"


def read(ctx):
    admit = (ctx.trace or {}).get("admit_device")
    if not admit or not admit["prefill_spans"]:
        return None
    return 1e3 * admit["other_s"] / admit["prefill_spans"]
