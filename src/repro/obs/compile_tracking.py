"""JIT compile and compilation-cache tracking via ``jax.monitoring``.

JAX reports each step of turning a jitted call into an executable:

* ``/jax/core/compile/jaxpr_trace_duration`` — tracing to a jaxpr;
* ``/jax/core/compile/jaxpr_to_mlir_module_duration`` — lowering to MLIR;
* ``/jax/core/compile/backend_compile_duration`` — the backend step,
  compile *or load*: it wraps ``compile_or_get_cached``, so it fires for
  an XLA compile and also for an executable loaded from the persistent
  compilation cache.  Jit-cache (in-memory) hits emit nothing.

and, when the persistent cache is on, ``/jax/compilation_cache/
cache_hits`` and ``cache_misses`` (a compiled executable written to the
cache) and ``cache_retrieval_time_sec`` (the seconds a hit spent
loading).  ``install()`` registers one listener set, idempotently;
``compile_stats()`` reads the running totals as one dict.

``compile_count()`` / ``compile_secs()`` count the backend step, loads
included.  They fill the ``jit_compiles`` gauge of every
``RunResult.metrics`` snapshot, which the recompile guard asserts on
(tests/test_obs.py: a second ``Federation`` run with an identical config
triggers zero backend steps — the memoized-jit contract).  A process that
loads its programs from a warm persistent cache counts each load here;
``compile_stats()["cache_hits"]`` says how many of them were loads.

Observers that trace subscribe (``subscribe``) to hear of each backend
step as ``(fun_name, seconds, cache_hit)``.
"""
from __future__ import annotations

import threading
import weakref

_STEPS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "backend"}
_HITS = "/jax/compilation_cache/cache_hits"
_MISSES = "/jax/compilation_cache/cache_misses"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_lock = threading.Lock()
_local = threading.local()        # a cache hit seen inside a backend step
_sinks: list = []                 # weakref.WeakMethod of subscribers
_state = {"installed": False,
          "trace_count": 0, "trace_s": 0.0, "lower_count": 0, "lower_s": 0.0,
          "backend_count": 0, "backend_s": 0.0,
          "cache_hits": 0, "cache_misses": 0, "cache_retrieval_s": 0.0}
# per step, the outermost intervals seen so far: a nested step (a jit
# traced while tracing another) ends first and is inside the next one
# to end, whose seconds then replace its own, so nesting counts once
_outer = {step: [] for step in _STEPS.values()}


def _on_span(event: str, start: float, end: float, **kw) -> None:
    step = _STEPS.get(event)
    if step is None:
        return
    with _lock:
        _state[f"{step}_count"] += 1
        stack = _outer[step]
        secs = end - start
        while stack and stack[-1][0] >= start:
            secs -= stack.pop()[1]
        stack.append((start, end - start))
        _state[f"{step}_s"] += secs
    if step == "backend":
        hit = getattr(_local, "hit", False)
        _local.hit = False
        for ref in list(_sinks):
            sink = ref()
            if sink is not None:
                sink(str(kw.get("fun_name", "")), end - start, hit)


def _on_event(event: str, **kw) -> None:
    if event == _HITS:
        _local.hit = True
        with _lock:
            _state["cache_hits"] += 1
    elif event == _MISSES:
        with _lock:
            _state["cache_misses"] += 1


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _RETRIEVAL:
        with _lock:
            _state["cache_retrieval_s"] += duration


def install() -> None:
    """Register the listeners (idempotent, process-wide)."""
    with _lock:
        if _state["installed"]:
            return
        _state["installed"] = True
    import jax.monitoring
    jax.monitoring.register_event_time_span_listener(_on_span)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_stats() -> dict:
    """Totals since ``install()``: ``{trace,lower,backend}_count`` and
    ``{trace,lower,backend}_s`` (seconds, a nested step counted once),
    ``cache_hits``, ``cache_misses`` and ``cache_retrieval_s``."""
    with _lock:
        return {k: v for k, v in _state.items() if k != "installed"}


def compile_count() -> int:
    """Backend steps (compiles and persistent-cache loads) since
    ``install()``."""
    return _state["backend_count"]


def compile_secs() -> float:
    """Seconds of backend steps since ``install()``."""
    return _state["backend_s"]


def subscribe(method) -> None:
    """Call the bound ``method(fun_name, seconds, cache_hit)`` after each
    backend step, until ``unsubscribe`` or until its object is gone."""
    with _lock:
        _sinks.append(weakref.WeakMethod(method))


def unsubscribe(method) -> None:
    with _lock:
        _sinks[:] = [r for r in _sinks
                     if r() is not None and r() != method]
