"""Share of the closed-loop window in which no operation ran on the device:
100 * (1 - busy / window), busy the union of the operations' intervals in
the profiler trace, window the traced run's host-clock length."""


def read(ctx):
    if ctx.driver != "closed_loop" or ctx.device["devices"] == 0:
        return None
    return 100.0 * (1.0 - ctx.device["busy_s"] / ctx.window_s)
