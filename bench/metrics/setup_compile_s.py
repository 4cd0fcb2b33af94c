"""Seconds of set-up spent turning jitted calls into executables: jaxpr
tracing, MLIR lowering and the backend step (an XLA compile, or a load from
the persistent compilation cache).  Read from the program's
"compile_totals" record, which the traced run writes at its start with the
process's totals since ``repro.obs.install()``; the closed-loop driver
installs at its entry, so the totals span its whole set-up (a step nested
in another counts once).  Read on a device of ``bench/peaks.json`` only: a
CPU backend compiles other programs."""


def read(ctx):
    if ctx.peaks is None:
        return None
    tot = next((e for e in ctx.spans if e.get("name") == "compile_totals"),
               None)
    if tot is None:
        return None
    return tot["trace_s"] + tot["lower_s"] + tot["backend_s"]
