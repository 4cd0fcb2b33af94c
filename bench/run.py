#!/usr/bin/env python3
"""Run one benchmark cell once on the chip it finds:

    python3 bench/run.py --workload paper7_vafl_topk --seed 7 --seconds 10 --trace 0

Prints one JSON object as the last line of standard output (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``; ``breakdown`` with
``--trace 1``; the compared numbers and their limits last, under
``checks``), and the compared numbers once more as the last lines of
standard error.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics.  Exits non-zero, with no
result line, when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None,
                    help="directory to keep the extracted device events in")
    args = ap.parse_args(argv)

    from bench import harness
    try:
        line = harness.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START, dump=args.dump)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for text in harness.check_lines(line):
        print(text, file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
