#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload paper7_vafl_topk \\
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 1,2,3

In one process, for each seed: the program's first engine window (the
timed path's ``Federation.run`` with its run-state checkpoint) against the
float32 HIGHEST reference (``--set reference=...`` computes the reference
in other blocks of clients: another sound arithmetic); and for each control seed also the control (the
reference computed in bfloat16, in the program's place) and each planted
fault (``reference.FAULTS``, planted in the reference in the program's
place).  Prints one JSON line per seed and side with the compared numbers,
the smallest gate margin, and the seconds each part took.  Needs the chip
as ``run.py`` does; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _margin(ref: dict):
    m = [abs(v) for v in ref.get("margins", {}).values()]
    return min(m) if m else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="KEY=JSON: replace a key of the configuration")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    from bench import harness
    override = {}
    for item in args.set:
        k, v = item.split("=", 1)
        override[k] = json.loads(v)
    spec = harness.load(args.workload, config_override=override)
    import jax
    try:
        harness.device_info(jax, spec["cell"]["chips"], True)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.enable_cache(jax)
    if "matmul_precision" in spec["config"]:
        jax.config.update("jax_default_matmul_precision",
                          spec["config"]["matmul_precision"])
    sys.path.insert(0, str(harness.ROOT / "src"))
    from bench import closed_loop, compare, datagen, reference

    cfg, traffic, cell = spec["config"], spec["traffic"], spec["cell_file"]
    n, tie = cfg["num_clients"], cell["tie_margin"]
    out = open(args.out, "w") if args.out else None

    def emit(rec):
        text = json.dumps(rec)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()

    for seed in seeds:
        data = datagen.make(seed, cfg["data"], n)
        fed, fd = closed_loop._build(cfg, traffic, data, seed)
        dev = {**data, **{k: getattr(fd, k)
                          for k in ("images", "labels", "mask")}}
        t = time.perf_counter()
        state, _ = closed_loop._warm_up(fed, n, 1)
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        fed.run(mode="event", rounds=1)
        closed_loop._drain(jax)
        t_window = time.perf_counter() - t
        side, follow = closed_loop._program_side(fed, state, traffic, seed)
        del fed, state
        t = time.perf_counter()
        upd = reference.updates(cfg, traffic, dev, seed)
        jax.block_until_ready(upd["new"])
        t_upd = time.perf_counter() - t
        ref = reference.commits(cfg, traffic, upd, seed, follow=follow,
                                tie_margin=tie)
        t_ref = time.perf_counter() - t
        detail = {}
        found = compare.numbers(side, ref, detail)
        emit({"seed": seed, "side": "program", "numbers": found,
              "detail": detail,
              "min_margin": _margin(ref), "flushes": ref["flushes"],
              "uploads": sum(ref["decisions"].values()),
              "program_s": t_prog, "window_s": t_window,
              "reference_s": t_ref,
              "reference_updates_s": t_upd})
        if seed not in control:
            continue
        variants = [("control", reference.control_kw(cfg))]
        variants += [(f, dict(fault=f)) for f in reference.FAULTS]
        for name, kw in variants:
            t = time.perf_counter()
            other = reference.commits(
                cfg, traffic, reference.updates(cfg, traffic, dev, seed,
                                                **kw), seed)
            ref = reference.commits(cfg, traffic, upd, seed,
                                    follow=other["decisions"]
                                    if traffic["algorithm"] == "vafl"
                                    else None, tie_margin=tie)
            detail = {}
            emit({"seed": seed, "side": name,
                  "numbers": compare.numbers(other, ref, detail),
                  "detail": detail,
                  "seconds": time.perf_counter() - t})
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
