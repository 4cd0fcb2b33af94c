"""Device time of the Eq. 1 value term (the per-client accuracy scan and the
values program) per committed event (ms)."""


def read(ctx):
    s = ctx.device["layer_s"].get("value_term")
    if not s:
        return None
    return 1e3 * s / ctx.events
