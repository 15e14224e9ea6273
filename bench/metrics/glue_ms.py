"""Device milliseconds per forward of the ops that belong to no conv layer:
the max pool, the mean and fc, and the copies that feed them."""


def read(ctx):
    if ctx.requests <= 0:
        return None
    return 1e3 * ctx.trace.category_s.get("glue", 0.0) / ctx.requests
