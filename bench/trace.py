"""From a profiler trace to device times: the reduction every cell shares.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps,
for each accelerator plane, two lists of events: the compiled programs
("XLA Modules") and the operations inside them ("XLA Ops"), each as
``[name, start_ns, duration_ns]`` (an operation also names its program).
``reduce`` turns those lists into what the per-layer metrics read:

* ``busy_s``    the union of the operations' intervals, averaged over the
                devices in the trace;
* ``layer_s``   device seconds of the programs of each layer, a program
                assigned to the layer whose name pattern in
                ``layers.json`` matches it first;
* ``kernel_s``  device seconds and calls of each named kernel
                (``layers.json`` "kernels": a pattern on operation names);
* ``top_ops``   the ten operations that took most device time;
* ``idle_gaps`` the ten longest gaps between programs, each named by the
                programs on either side.
"""
from __future__ import annotations

import glob
import json
import os
import re

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def extract(trace_dir: str) -> dict:
    """{device plane name: {"modules": [...], "ops": [...]}} of the newest
    trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if MODULE_LINE not in lines:
            continue
        dev = {"modules": [[e.name, e.start_ns, e.duration_ns]
                           for e in lines[MODULE_LINE].events], "ops": []}
        if OP_LINE in lines:
            for e in lines[OP_LINE].events:
                stats = dict(e.stats)
                dev["ops"].append([e.name, e.start_ns, e.duration_ns,
                                   str(stats.get("hlo_module", ""))])
        out[plane.name] = dev
    return out


def describe(trace_dir: str, samples: int = 3) -> list:
    """Every plane and line of the newest trace, with its event count and
    a few events with their stats: for reading a trace by hand."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        for line in plane.lines:
            evs = list(line.events)
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(evs),
                        "sample": [[e.name, e.start_ns, e.duration_ns,
                                    {k: str(v) for k, v in e.stats}]
                                   for e in evs[:samples]]})
    return out


def _union_ns(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, d in sorted((s, d) for s, d in intervals):
        e = s + d
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _module_base(name: str) -> str:
    """'jit_update_keyed(12)' -> 'jit_update_keyed'."""
    return re.sub(r"\(\d+\)$", "", name)


def layer_of(module: str, layers: dict):
    base = _module_base(module)
    for layer, patterns in layers["programs"].items():
        if any(re.search(p, base) for p in patterns):
            return layer
    return None


def reduce(devices: dict, layers: dict) -> dict:
    """What the per-layer metrics read, from ``extract``'s output."""
    if not devices:
        return {"devices": 0, "busy_s": 0.0, "layer_s": {}, "kernel_s": {},
                "top_ops": [], "idle_gaps": [], "unassigned_s": {}}
    busy, layer_s, kernel_s, op_s, unassigned = 0.0, {}, {}, {}, {}
    gaps = []
    for dev in devices.values():
        mods = sorted(dev["modules"], key=lambda m: m[1])
        spans = ([(o[1], o[2]) for o in dev["ops"]] if dev["ops"]
                 else [(m[1], m[2]) for m in mods])
        busy += _union_ns(spans) * 1e-9
        for name, _, dur in mods:
            layer = layer_of(name, layers)
            if layer is None:
                base = _module_base(name)
                unassigned[base] = unassigned.get(base, 0.0) + dur * 1e-9
            else:
                layer_s[layer] = layer_s.get(layer, 0.0) + dur * 1e-9
        for name, _, dur, _mod in dev["ops"]:
            op_s[name] = op_s.get(name, 0.0) + dur * 1e-9
            for kernel, pattern in layers["kernels"].items():
                if re.search(pattern, name):
                    k = kernel_s.setdefault(kernel, {"s": 0.0, "calls": 0})
                    k["s"] += dur * 1e-9
                    k["calls"] += 1
        for a, b in zip(mods, mods[1:]):
            gap = b[1] - (a[1] + a[2])
            if gap > 0:
                gaps.append((gap * 1e-9, f"{_module_base(a[0])} -> "
                                         f"{_module_base(b[0])}"))
    n = len(devices)
    gaps.sort(reverse=True)
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return {"devices": n, "busy_s": busy / n,
            "layer_s": {k: v / n for k, v in layer_s.items()},
            "kernel_s": {k: {"s": v["s"] / n, "calls": v["calls"] / n}
                         for k, v in kernel_s.items()},
            "unassigned_s": {k: v / n for k, v in unassigned.items()},
            "top_ops": [[k, v / n] for k, v in top],
            "idle_gaps": [[name, s] for s, name in gaps[:10]]}


def load_layers(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
