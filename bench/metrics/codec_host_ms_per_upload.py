"""Host time of the upload codec per accepted upload (ms): the program's
``repro.obs`` "encode" spans (error feedback, top-k + int8 encode and the
server's decode of one upload) summed over the window, over its uploads."""


def read(ctx):
    durs = [e["host_dur"] for e in ctx.spans
            if e.get("name") == "encode" and "host_dur" in e]
    if not durs or not ctx.uploads:
        return None
    return 1e3 * sum(durs) / ctx.uploads
