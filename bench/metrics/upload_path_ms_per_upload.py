"""Host time of the path of one accepted upload (ms): the program's
``repro.obs`` "upload_path" spans (the client's rows, the tree delta, the
codec with its "encode" span, the reconstruction, the buffer append and
the mix it triggers) summed over the window, over its uploads.  Read on a
device of ``bench/peaks.json`` only, as the roofline and MFU are: host
times beside a CPU backend are not the chip's."""


def read(ctx):
    if ctx.peaks is None or not ctx.uploads:
        return None
    durs = [e["host_dur"] for e in ctx.spans
            if e.get("name") == "upload_path" and "host_dur" in e]
    if not durs:
        return None
    return 1e3 * sum(durs) / ctx.uploads
