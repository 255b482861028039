"""Share of set-up's compile requests served by the persistent cache
(`jax.monitoring` cache events).  Nothing requested: nothing to read."""


def read(ctx):
    if not ctx.get("cache_requests"):
        return None
    return 100.0 * ctx["cache_hits"] / ctx["cache_requests"]
