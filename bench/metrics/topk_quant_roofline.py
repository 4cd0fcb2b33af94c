"""Share of its roofline the topk_quant kernel reached (%): the least time
the chip could take for the bytes and operations of every call, computed
from the kernel's shapes, over the kernel's device time in the trace.  The
model's padded flat width sets the shape of every call."""


def read(ctx):
    if ctx.peaks is None:
        return None
    k = ctx.device["kernel_s"].get("topk_quant")
    if not k or not k["s"]:
        return None
    f = ctx.flops
    rows = f.topk_quant_rows(f.cnn_params(ctx.cfg["model"]))
    least = max(f.topk_quant_bytes(rows) / ctx.peaks["hbm_bytes_per_s"],
                f.topk_quant_ops(rows) / ctx.peaks["int8_ops"])
    return 100.0 * least * k["calls"] / k["s"]
