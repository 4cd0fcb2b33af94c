"""The program's host spans on the device trace's clock, and the device's
idle time put down to them.

The program (``repro.obs``, when its spans are on) opens each host span as
a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``.  In a
``jax.profiler`` trace those lie on the host plane, on the same clock as
the device planes that ``bench/trace.py`` reads.

* ``read(trace_dir)`` the spans of the newest trace under ``trace_dir``,
  each ``[name, start_ns, duration_ns, tags]``, ``name`` without its
  prefix;
* ``attribute(devices, spans)`` takes each stretch of device idle time
  (per device, from the start of the run's ``run.start`` span to the end
  of its ``run.finish``) and puts it down to the innermost span open over
  it (the one that started last), or to ``unattributed``:

  - ``idle_by_span``  device-idle seconds under each innermost span name,
                      averaged over the devices;
  - ``idle_within``   device-idle seconds under any open span of each
                      name (a span's own and that of the spans inside it);
  - ``span_window_s`` the length of the stretch from run start to finish;

* ``gap_span(segments(spans), start_ns, end_ns)`` the span that held most
  of one idle gap, or None where no span did.

``devices`` is the output of ``bench/trace.py``'s ``extract``.
"""
from __future__ import annotations

import glob
import os

HOST_PLANE = "/host:CPU"
PREFIX = "repro."
RUN_START, RUN_FINISH = "run.start", "run.finish"
UNATTRIBUTED = "unattributed"


def read(trace_dir: str) -> list:
    """The ``repro.*`` events of the host plane of the newest trace under
    ``trace_dir``, by start."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append([e.name[len(PREFIX):], e.start_ns,
                                e.duration_ns, dict(e.stats)])
    out.sort(key=lambda sp: sp[1])
    return out


def _merged(intervals) -> list:
    """``(start, duration)`` intervals as the sorted, disjoint
    ``[start, end]`` pieces of their union."""
    out = []
    for s, d in sorted(intervals):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _idle(busy: list, t0: float, t1: float) -> list:
    """The pieces of ``[t0, t1]`` that no merged busy interval covers."""
    out, at = [], t0
    for s, e in busy:
        if e <= at:
            continue
        if s >= t1:
            break
        if s > at:
            out.append((at, s))
        at = e
    if at < t1:
        out.append((at, t1))
    return out


def segments(spans) -> list:
    """The spans cut into ``(start, end, innermost, open names)`` pieces
    over which the same spans are open; the innermost is the open span
    that started last (of two that started together, the shorter)."""
    cuts = sorted([(s, 1, k) for k, (_, s, d, _t) in enumerate(spans)
                   if d > 0] +
                  [(s + d, 0, k) for k, (_, s, d, _t) in enumerate(spans)
                   if d > 0])
    out, open_, at = [], {}, None
    for t, starts, k in cuts:
        if open_ and t > at:
            inner = max(open_.values(), key=lambda sp: (sp[1], -sp[2]))
            out.append((at, t, inner[0],
                        frozenset(sp[0] for sp in open_.values())))
        at = t
        if starts:
            open_[k] = spans[k][:3]
        else:
            open_.pop(k)
    return out


def _split(idle, segs):
    """Seconds of the sorted, disjoint ``idle`` pieces under each
    innermost span name (``UNATTRIBUTED`` where none is open), and under
    each open span name."""
    inner, within = {UNATTRIBUTED: 0.0}, {}
    k = 0
    for a, b in idle:
        while k < len(segs) and segs[k][1] <= a:
            k += 1
        covered = 0.0
        for m in range(k, len(segs)):
            s, e, name, names = segs[m]
            if s >= b:
                break
            part = min(b, e) - max(a, s)
            if part > 0:
                covered += part
                inner[name] = inner.get(name, 0.0) + part * 1e-9
                for n in names:
                    within[n] = within.get(n, 0.0) + part * 1e-9
        inner[UNATTRIBUTED] += (b - a - covered) * 1e-9
    return inner, within


def run_window(spans):
    """``(start_ns, end_ns)`` from the first ``run.start`` to the end of the
    last ``run.finish``, or None when the spans hold no whole run."""
    starts = [s for name, s, _, _ in spans if name == RUN_START]
    ends = [s + d for name, s, d, _ in spans if name == RUN_FINISH]
    if not starts or not ends or max(ends) <= min(starts):
        return None
    return min(starts), max(ends)


def attribute(devices: dict, spans) -> dict:
    """The devices' idle time over the run, put down to the spans (empty
    dicts where the spans hold no whole run or there is no device)."""
    run = run_window(spans)
    if run is None or not devices:
        return {"idle_by_span": {}, "idle_within": {}, "span_window_s": 0.0}
    segs = segments(spans)
    by, within = {}, {}
    for dev in devices.values():
        busy = _merged([(o[1], o[2]) for o in dev["ops"]] if dev["ops"]
                       else [(m[1], m[2]) for m in dev["modules"]])
        for into, part in zip((by, within), _split(_idle(busy, *run),
                                                   segs)):
            for k, v in part.items():
                into[k] = into.get(k, 0.0) + v
    n = len(devices)
    return {"idle_by_span": {k: v / n for k, v in by.items()},
            "idle_within": {k: v / n for k, v in within.items()},
            "span_window_s": (run[1] - run[0]) * 1e-9}


def gap_span(segs, start_ns: float, end_ns: float):
    """The innermost span that held most of ``[start_ns, end_ns]``, or
    None where that was no span."""
    inner, _ = _split([(start_ns, end_ns)], segs)
    name = max(inner, key=inner.get)
    return None if name == UNATTRIBUTED else name
