"""Engine admission: host milliseconds inside ``admit`` per admitted
request, over the window.  Each call prefills its requests, scatters their
pages and ends in the first tokens' transfer."""
UNIT = "ms"


def read(ctx):
    n = sum(k for _, _, k in ctx.admit_calls)
    if not n:
        return None
    return 1e3 * sum(b - a for a, b, _ in ctx.admit_calls) / n
