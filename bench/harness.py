"""Run one cell of the benchmark once and print its result line.

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration (``bench/configs/<config>.json``) and its traffic
(``bench/traffic/<traffic>.json``); the traffic names its driver
(``bench/<driver>.py``); ``bench/cells/<cell>.json`` holds the cell's
nominal window length and the limits of its comparison; each per-layer
metric is read by ``bench/metrics/<metric>.py``; ``bench/peaks.json`` and
``bench/layers.json`` are the peaks per device kind and the map from
programs to layers.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".bench_cache" / "jax"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load(workload: str, root: Path = ROOT, config_override=None) -> dict:
    """The cell's whole specification, resolved from the files."""
    bm = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{', '.join(cells)}")
    cell = cells[workload]
    entry = next(c for c in bm["configs"] if c["name"] == cell["config"])
    config = _read(root / entry["file"])
    for k, v in (config_override or {}).items():
        config[k] = v
    applies = (lambda m: workload in m["workloads"] if "workloads" in m
               else True)
    e2e = [m for m in bm["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return {"workload": workload, "cell": cell, "config": config,
            "traffic": _read(BENCH / "traffic" / f"{cell['traffic']}.json"),
            "cell_file": _read(BENCH / "cells" / f"{workload}.json"),
            "end_to_end": e2e, "per_layer": per_layer,
            "peaks_table": _read(BENCH / "peaks.json"),
            "layers": str(BENCH / "layers.json")}


def device_info(jax, chips: int, require_chip: bool) -> dict:
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX finds "
                     f"{len(devices)} {dev.platform} device(s)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def peaks_for(table: dict, kind: str, require_chip: bool):
    if kind in table["devices"]:
        return table["devices"][kind]
    if require_chip:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return None


def enable_cache(jax) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached however fast it compiled."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def read_metric(name: str, ctx):
    """Run ``bench/metrics/<name>.py``'s ``read(ctx)``: a number, or None
    when the trace holds nothing for it."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx)


def run(workload: str, seed: int, seconds: float, traced: bool,
        t_start: float, *, require_chip: bool = True, cache: bool = True,
        config_override=None, dump: str = None) -> dict:
    """One run of one cell; returns the result line as a dict."""
    spec = load(workload, config_override=config_override)
    import jax
    device = device_info(jax, spec["cell"]["chips"], require_chip)
    spec["peaks"] = peaks_for(spec["peaks_table"], device["kind"],
                              require_chip)
    if cache:
        enable_cache(jax)
    if "matmul_precision" in spec["config"]:
        # the precision the configuration states for the program's float32
        # matrix products (JAX's own default on a TPU is one bfloat16 pass)
        jax.config.update("jax_default_matmul_precision",
                          spec["config"]["matmul_precision"])
    sys.path.insert(0, str(ROOT / "src"))
    driver = importlib.import_module(f"bench.{spec['traffic']['driver']}")
    with tempfile.TemporaryDirectory() as out_dir:
        out = driver.run(spec, seed, seconds, traced, t_start, out_dir)
        if dump:
            _dump(dump, out_dir, out)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    metrics = {}
    if traced:
        device["busy_s"] = out["busy_s"]
        device["window_s"] = out["notes"]["window_s"]
        for m in spec["per_layer"]:
            v = read_metric(m["name"], out["ctx"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(out["e2e"], setup_s=out["setup_s"])
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if traced:
        line["breakdown"] = out["breakdown"]
    line["notes"] = out["notes"]
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, v, lim in out["checks"]}
    return line


def _dump(dump: str, out_dir: str, out: dict) -> None:
    """Keep the extracted device events and the run's notes (for reading a
    trace by hand)."""
    os.makedirs(dump, exist_ok=True)
    trace_dir = os.path.join(out_dir, "trace")
    if os.path.isdir(trace_dir):
        from bench import trace
        with open(os.path.join(dump, "planes.json"), "w") as f:
            json.dump(trace.describe(trace_dir), f, indent=1)
        devices = trace.extract(trace_dir)
        kept = {p: {"modules": d["modules"][:3000], "ops": d["ops"][:20000]}
                for p, d in devices.items()}
        with open(os.path.join(dump, "device_events.json"), "w") as f:
            json.dump(kept, f)
    with open(os.path.join(dump, "notes.json"), "w") as f:
        json.dump(out["notes"], f, default=str)


def check_lines(line: dict) -> list:
    """The compared numbers as plain lines, for the end of stderr."""
    return [f"check {k} {c['value']!r} limit {c['limit']!r}"
            for k, c in line["checks"].items()]

