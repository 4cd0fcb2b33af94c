"""Device time of the local-update programs per committed event (ms)."""


def read(ctx):
    s = ctx.device["layer_s"].get("local_update")
    if not s:
        return None
    return 1e3 * s / ctx.events
