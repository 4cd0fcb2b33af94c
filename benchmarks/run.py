"""Benchmark entry point — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--full]

Sections:
  [table3]   paper Table III — comm times + CCR, experiments a-d
  [fig4]     paper Fig. 4    — convergence curves per algorithm
  [fig5/6]   paper Fig. 5/6  — per-client + cross-experiment VAFL Acc
  [compress] codec x algorithm uplink-bytes/CCR sweep (repro.compress)
  [engine]   batched async engine events/sec + accuracy at N up to 1024
  [scenarios] repro.sim scenario x algorithm x codec time-to-accuracy
  [obs]      repro.obs tracing/metrics overhead + trace-export checks
  [analysis] repro.analysis static gate over src/benchmarks/examples
  [serving]  repro.serve live-service load generator (uploads/sec,
             queue depth, commit latency under paper_testbed traffic)
  [resilience] repro.resilience chaos soak + checkpoint-resume (seeded
             fault injection, retry/dedup reconciliation, restore time)
  [trend]    cross-PR trend: every BENCH_*.json's headline numbers
             appended to BENCH_trend.json with regression bands
  [kernels]  grad_diff_norm / linear_scan microbenchmarks
  [roofline] three-term roofline per (arch x shape) from dry-run artifacts
  [gated]    cross-pod gated-collective accounting (multi-pod artifacts)

--fast shrinks rounds/samples (CI-friendly); default is the BenchScale
configuration in benchmarks/fl_common.py; --full approaches paper scale
(slow on CPU).
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="minimal public-API sweep (CI tier-1; see "
                         "tests/test_public_api.py)")
    ap.add_argument("--skip", default="", help="comma list of sections")
    args = ap.parse_args()
    skip = set(args.skip.split(",")) if args.skip else set()

    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks.fl_common import BenchScale
    if args.smoke:
        scale = BenchScale(samples_per_client=120, rounds=2,
                           test_samples=200, target_acc=0.5)
        exps = ["a"]
        skip |= {"ablation", "kernels", "roofline", "gated"}
    elif args.fast:
        scale = BenchScale(samples_per_client=400, rounds=8, test_samples=500,
                           target_acc=0.90)
        exps = ["a", "c"]
    elif args.full:
        scale = BenchScale(samples_per_client=2500, rounds=60,
                           test_samples=2000, local_rounds=5)
        exps = None
    else:
        scale = BenchScale()
        exps = None

    if "table3" not in skip:
        print("== [table3] communication times + CCR (paper Table III) ==")
        from benchmarks.table3_ccr import run as t3
        t3(scale=scale, experiments=exps,
           out_json="artifacts/table3.json" if os.path.isdir("artifacts") else None)
        print()

    if "fig4" not in skip:
        print("== [fig4] convergence curves (paper Fig. 4) ==")
        from benchmarks.fig4_convergence import run as f4
        f4(scale=scale, experiments=exps or ["a", "d"],
           png="artifacts/fig4.png" if os.path.isdir("artifacts") else None)
        print()

    if "fig5" not in skip:
        print("== [fig5/6] per-client Acc under VAFL (paper Fig. 5/6) ==")
        from benchmarks.fig5_clients import run as f5
        f5(scale=scale, experiments=exps or ["a", "d"])
        print()

    if "ablation" not in skip and not args.fast:
        print("== [ablation] Eq.1 ingredients (clean + 2 corrupted clients) ==")
        from benchmarks.ablation_value import run as ab
        from benchmarks.fl_common import BenchScale as BS
        ab("d", BS(samples_per_client=600, rounds=12, test_samples=500,
                   target_acc=0.94), corrupt_clients=2)
        print()

    if "compress" not in skip:
        print("== [compress] codec x algorithm uplink sweep ==")
        from benchmarks.compress_bench import run as cb
        cb(scale=scale,
           out_json="artifacts/compress.json" if os.path.isdir("artifacts")
           else None)
        print()

    if "engine" not in skip:
        print("== [engine] batched async engine scale sweep ==")
        from benchmarks.async_engine_bench import run as eng
        # same scale contract as the other sections: default stays
        # moderate, --full adds the N=1024 lap, --fast runs the smoke sweep.
        # Always emits the machine-readable BENCH_engine.json (events/sec
        # per engine/N + byte CCR) so the perf trajectory is tracked
        # across PRs — tier-1 asserts it (tests/test_public_api.py).
        eng((16,) if args.smoke else
            (64, 256, 1024) if args.full else (64, 256),
            smoke=args.fast or args.smoke,
            out_json=os.path.join(
                "artifacts" if os.path.isdir("artifacts") else "",
                "BENCH_engine.json"))
        print()

    if "scenarios" not in skip:
        print("== [scenarios] scenario x algorithm x codec "
              "time-to-accuracy (repro.sim) ==")
        from benchmarks.scenario_bench import run as sb
        # always emits the machine-readable BENCH_scenarios.json —
        # tier-1 asserts it shows the byte-aware clock coupling (vafl +
        # topk_int8 reaches the target in less simulated time than
        # vafl + identity on the same scenario)
        sb(smoke=args.smoke or args.fast,
           out_json=os.path.join(
               "artifacts" if os.path.isdir("artifacts") else "",
               "BENCH_scenarios.json"))
        print()

    if "obs" not in skip:
        print("== [obs] observability overhead + trace export (repro.obs) ==")
        from benchmarks.obs_bench import run as ob
        # always emits the machine-readable BENCH_obs.json (schema
        # bench-obs/v1): obs-on vs obs-off lap time, trace event counts
        # reconciled against CommStats, bit-exactness — tier-1 asserts
        # it (tests/test_public_api.py); --full adds the N=1024 lap
        # where the <5% overhead contract is measured
        ob(smoke=args.smoke or args.fast, full=args.full,
           out_json=os.path.join(
               "artifacts" if os.path.isdir("artifacts") else "",
               "BENCH_obs.json"))
        print()

    if "analysis" not in skip:
        print("== [analysis] static-analysis gate (repro.analysis) ==")
        from benchmarks.analysis_gate import run as ag
        # always emits the machine-readable BENCH_analysis.json (schema
        # analysis-report/v1): the full rule set over the shipped tree
        # against the checked-in baseline — tier-1 asserts zero
        # unsuppressed findings (tests/test_public_api.py)
        ag(out_json=os.path.join(
            "artifacts" if os.path.isdir("artifacts") else "",
            "BENCH_analysis.json"))
        print()

    if "serving" not in skip:
        print("== [serving] live-service load generator (repro.serve) ==")
        from benchmarks.serving_bench import run as sv
        # always emits the machine-readable BENCH_serving.json (schema
        # bench-serving/v1): sustained uploads/sec, queue depth and
        # commit latency over a live inproc federation with concurrent
        # workers, obs counters reconciled against CommStats — tier-1
        # asserts it (tests/test_public_api.py)
        sv(smoke=args.smoke or args.fast,
           out_json=os.path.join(
               "artifacts" if os.path.isdir("artifacts") else "",
               "BENCH_serving.json"))
        print()

    if "resilience" not in skip:
        print("== [resilience] chaos soak + checkpoint-resume "
              "(repro.resilience) ==")
        from benchmarks.resilience_bench import run as rb
        # always emits the machine-readable BENCH_resilience.json (schema
        # bench-resilience/v1): the chaos lap's committed-update multiset
        # reconciled against the fault-free control (at-least-once retry
        # + seq dedup = exactly-once commit) plus checkpoint write/restore
        # economics — tier-1 asserts it (tests/test_public_api.py)
        rb(smoke=args.smoke or args.fast,
           out_json=os.path.join(
               "artifacts" if os.path.isdir("artifacts") else "",
               "BENCH_resilience.json"))
        print()

    if "kernels" not in skip:
        print("== [kernels] microbenchmarks ==")
        from benchmarks.kernel_bench import run as kb
        kb()
        print()

    if "roofline" not in skip and os.path.isdir("artifacts/dryrun"):
        print("== [roofline] per-(arch x shape) roofline terms ==")
        from benchmarks.roofline import run as rl
        rl("artifacts/dryrun", csv=True)
        print()

    if "gated" not in skip and os.path.isdir("artifacts/dryrun"):
        print("== [gated] cross-pod gated collective ==")
        from benchmarks.gated_collective import run as gc
        gc("artifacts/dryrun")
        print()

    if "trend" not in skip:
        print("== [trend] cross-PR benchmark trend (bench-trend/v1) ==")
        from benchmarks.trend import run as tb
        # last on purpose: folds every BENCH_*.json the sweep above just
        # emitted into one BENCH_trend.json lap (schema bench-trend/v1)
        # with direction-aware regression bands vs the previous lap —
        # tier-1 asserts the artifact (tests/test_public_api.py); a
        # --skip'd section simply drops out of the headline
        tb(out_json=os.path.join(
            "artifacts" if os.path.isdir("artifacts") else "",
            "BENCH_trend.json"))
        print()


if __name__ == "__main__":
    main()
