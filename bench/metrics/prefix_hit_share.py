"""Page pool: prompt pages found shared (``PagePool.shared_hits``) over the
full prompt pages admitted in the window."""
UNIT = "ratio"


def read(ctx):
    if not ctx.prompt_pages:
        return None
    return ctx.shared_hits / ctx.prompt_pages
