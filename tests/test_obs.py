"""Observability subsystem (repro.obs, docs/OBSERVABILITY.md).

The acceptance contract:

* **The trace is the run** — span/event counts reconcile with
  ``CommStats`` on all four runtimes (rounds, events, batched, sync):
  upload events == model_uploads, report n-sum == scalar_reports,
  broadcast n-sum == broadcasts, upload nbytes-sum ==
  upload_payload_bytes, eval spans == len(records).
* **Ledger reconciliation** — ``uplink_bytes == upload_payload_bytes +
  scalar_report_bytes`` everywhere; per-client ledgers sum to
  ``uplink_bytes`` on the event-driven runtimes and to
  ``upload_payload_bytes`` on the round/sync runtimes.
* **Bit-exactness** — obs on vs off changes NOTHING in the numeric
  outputs (records, CommStats, client ledgers) on any runtime.
* **Determinism** — two identical traced runs emit identical event
  streams modulo host timestamps.
* **Recompile guard** — a second run of the SAME ``Federation`` triggers
  zero new backend compiles (the memoized-jit contract), asserted via
  the ``jit_compiles`` gauge fed by ``jax.monitoring``.
"""
import dataclasses
import json
import os

import pytest

from repro.core import Federation, FLRunConfig, run_event_driven, \
    run_round_based
from repro.core.client import (LocalSpec, make_evaluator,
                               make_weighted_classifier_loss)
from repro.data.partition import iid_partition
from repro.data.synthetic import synthetic_mnist
from repro.models.cnn import MLPConfig, mlp_forward, mlp_init
from repro.obs import (MetricsRegistry, ObsConfig, Tracer, read_jsonl,
                       resolve_obs)
from repro.obs.exporters import console_summary, write_chrome_trace
from repro.obs.metrics import Histogram
from repro.obs.observer import Observer

N = 5

# the four runtimes as (name, algorithm, runner kwargs)
RUNTIMES = [
    ("rounds", "vafl", dict(mode="round")),
    ("events", "vafl", dict(mode="event")),
    ("batched", "vafl", dict(mode="event", engine="batched",
                             max_batch=3, buffer_size=2)),
    ("sync", "fedavg", dict(mode="event")),
]


@pytest.fixture(scope="module")
def setup():
    xtr, ytr, xte, yte = synthetic_mnist(N * 120 + 300, 300, seed=0)
    mcfg = MLPConfig(hidden=(32,))
    loss_fn = make_weighted_classifier_loss(mlp_forward, mcfg)
    evaluate = make_evaluator(mlp_forward, mcfg, xte, yte, batch=300)
    fed = iid_partition(xtr, ytr, N, samples_per_client=120, seed=0)
    return mcfg, loss_fn, evaluate, fed


def _run(setup, alg, mode, rounds=3, **kw):
    mcfg, loss_fn, evaluate, fed = setup
    rc = FLRunConfig(algorithm=alg, num_clients=N, rounds=rounds,
                     local=LocalSpec(batch_size=32, local_rounds=1, lr=0.1),
                     target_acc=0.99, events_per_eval=N, **kw)
    runner = run_event_driven if mode == "event" else run_round_based
    return runner(rc, init_params_fn=lambda k: mlp_init(mcfg, k),
                  loss_fn=loss_fn, fed_data=fed, evaluate_fn=evaluate)


def _traced(setup, alg, runner_kw, tmp_path, tag, **kw):
    """Run with a JSONL trace and return (result, header, events)."""
    path = str(tmp_path / f"{tag}.jsonl")
    runner_kw = dict(runner_kw)
    mode = runner_kw.pop("mode")
    res = _run(setup, alg, mode, obs=ObsConfig(trace_jsonl=path),
               **runner_kw, **kw)
    header, events = read_jsonl(path)
    return res, header, events


def _numeric(res):
    """Everything numeric a run produces (the bit-exactness surface)."""
    return ([(r.round, r.time, r.global_acc, r.uploads_so_far,
              r.boundaries_crossed) for r in res.records],
            dataclasses.asdict(res.comm),
            res.sim_time, res.client_uplink_bytes, res.client_downlink_bytes)


# --------------------------------------------- trace <-> CommStats ---

class TestTraceReconciliation:
    @pytest.mark.parametrize("name,alg,kw", RUNTIMES,
                             ids=[r[0] for r in RUNTIMES])
    def test_trace_counts_match_commstats(self, setup, tmp_path, name,
                                          alg, kw):
        res, header, events = _traced(setup, alg, kw, tmp_path, name)
        by = {}
        for e in events:
            by.setdefault(e["name"], []).append(e)

        uploads = by.get("upload", [])
        assert len(uploads) == res.comm.model_uploads
        assert sum(e["nbytes"] for e in uploads) \
            == res.comm.upload_payload_bytes
        assert sum(e["n"] for e in by.get("report", [])) \
            == res.comm.scalar_reports
        bcasts = by.get("broadcast", [])
        assert sum(e["n"] for e in bcasts) == res.comm.broadcasts
        assert sum(e["nbytes"] for e in bcasts) == res.comm.downlink_bytes
        evals = by.get("eval", [])
        assert len(evals) == len(res.records)
        assert sum(e["boundaries"] for e in evals) \
            == sum(r.boundaries_crossed for r in res.records)
        # and the metrics registry agrees with both
        c = res.metrics["counters"]
        assert c["uploads"] == res.comm.model_uploads
        assert c.get("upload_payload_bytes", 0) \
            == res.comm.upload_payload_bytes
        assert c.get("scalar_reports", 0) == res.comm.scalar_reports
        assert c.get("broadcasts", 0) == res.comm.broadcasts
        assert c["evals"] == len(res.records)
        assert c["trace_events"] == len(events) == header["events"]

    @pytest.mark.parametrize("name,alg,kw", RUNTIMES,
                             ids=[r[0] for r in RUNTIMES])
    def test_upload_events_carry_tags(self, setup, tmp_path, name, alg, kw):
        res, _, events = _traced(setup, alg, kw, tmp_path, f"tag_{name}")
        for e in events:
            if e["name"] == "upload":
                assert e["client"] in range(N)
                assert e["staleness"] >= 0
                assert e["nbytes"] > 0
                assert e["codec"] == "identity"
                assert "sim" in e and "host" in e

    def test_staleness_recorded_async(self, setup, tmp_path):
        # buffered batched engine: aggregation lags uploads, so some
        # recorded staleness must be positive
        res, _, events = _traced(
            setup, "vafl", dict(mode="event", engine="batched",
                                max_batch=3, buffer_size=3),
            tmp_path, "stale", rounds=4)
        stale = [e["staleness"] for e in events if e["name"] == "upload"]
        assert stale and max(stale) > 0
        h = res.metrics["histograms"]["staleness"]
        assert h["count"] == len(stale)
        assert h["max"] == max(stale)

    def test_windows_and_flushes_traced(self, setup, tmp_path):
        res, _, events = _traced(
            setup, "vafl", dict(mode="event", engine="batched",
                                max_batch=3, buffer_size=2),
            tmp_path, "win", rounds=4)
        windows = [e for e in events if e["name"] == "window"]
        flushes = [e for e in events if e["name"] == "flush"]
        assert windows and all(e["ph"] == "X" and e["size"] >= 1
                               for e in windows)
        assert flushes and all(e["k"] >= 1 for e in flushes)
        assert res.metrics["counters"]["windows"] == len(windows)
        assert res.metrics["counters"]["flushes"] == len(flushes)


# ------------------------------------------------ ledger cross-check ---

class TestCommStatsLedger:
    @pytest.mark.parametrize("name,alg,kw", RUNTIMES,
                             ids=[r[0] for r in RUNTIMES])
    def test_uplink_ledger(self, setup, name, alg, kw):
        kw = dict(kw)
        mode = kw.pop("mode")
        res = _run(setup, alg, mode, **kw)
        c = res.comm
        assert c.uplink_bytes == c.upload_payload_bytes \
            + c.scalar_report_bytes
        assert c.scalar_report_bytes == 4 * c.scalar_reports
        assert c.total_wire_bytes == c.uplink_bytes + c.downlink_bytes
        if res.client_uplink_bytes is not None:
            total = sum(res.client_uplink_bytes)
            if name in ("events", "batched", "sync"):
                assert total == c.uplink_bytes
            else:
                assert total == c.upload_payload_bytes

    def test_vafl_reports_cost_bytes(self, setup):
        # VAFL's whole point: scalar reports instead of uploads — their
        # wire cost must be visible in uplink_bytes, not hidden
        res = _run(setup, "vafl", "event")
        assert res.comm.scalar_reports > 0
        assert res.comm.uplink_bytes > res.comm.upload_payload_bytes


# -------------------------------------------------- bit-exactness ---

class TestBitExact:
    @pytest.mark.parametrize("name,alg,kw", RUNTIMES,
                             ids=[r[0] for r in RUNTIMES])
    def test_obs_on_is_bit_exact(self, setup, name, alg, kw):
        kw = dict(kw)
        mode = kw.pop("mode")
        off = _run(setup, alg, mode, **kw)
        on = _run(setup, alg, mode, obs=True, **kw)
        assert _numeric(off) == _numeric(on)

    def test_obs_off_opens_no_annotation(self, setup, monkeypatch):
        """Off is off for the profiler too: a run with obs=None opens no
        ``repro.*`` TraceAnnotation, one with obs on opens its spans."""
        import jax.profiler

        opened = []
        real = jax.profiler.TraceAnnotation

        class Counting(real):
            def __init__(self, name, **tags):
                opened.append(name)
                super().__init__(name, **tags)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
        kw = dict(engine="batched", max_batch=3, buffer_size=2)
        _run(setup, "vafl", "event", **kw)
        assert not [n for n in opened if n.startswith("repro.")]
        _run(setup, "vafl", "event", obs=True, **kw)
        assert {"repro.run.start", "repro.window", "repro.window.decide",
                "repro.run.finish"} <= set(opened)

    def test_deterministic_trace(self, setup, tmp_path):
        kw = dict(mode="event", engine="batched", max_batch=3,
                  buffer_size=2)
        _, _, ev1 = _traced(setup, "vafl", kw, tmp_path, "det1")
        _, _, ev2 = _traced(setup, "vafl", kw, tmp_path, "det2")

        def strip_host(events):
            # compile records say what this process had compiled so far
            # or had yet to compile, which differs between two identical
            # runs in one process
            return [{k: v for k, v in e.items()
                     if k not in ("host", "host_dur")} for e in events
                    if e["name"] not in ("compile", "compile_totals")]
        assert strip_host(ev1) == strip_host(ev2)


# ------------------------------------------------ recompile guard ---

class TestRecompileGuard:
    @pytest.mark.parametrize("engine", ["sequential", "batched"])
    def test_second_run_compiles_nothing(self, setup, engine):
        """The memoized-jit contract: rerunning the SAME Federation must
        hit every jit cache — the jax.monitoring-fed gauge reads 0."""
        mcfg, _, _, fed = setup
        xtr, ytr, xte, yte = synthetic_mnist(N * 120 + 300, 300, seed=0)
        f = Federation(model=(mlp_forward, mlp_init, mcfg), data=fed,
                       test_data=(xte, yte), algorithm="vafl",
                       local=LocalSpec(batch_size=32, local_rounds=1,
                                       lr=0.1),
                       rounds=3, target_acc=0.99, seed=0, obs=True)
        kw = dict(engine="batched", max_batch=3) \
            if engine == "batched" else {}
        first = f.run(mode="event", **kw)
        second = f.run(mode="event", **kw)
        assert second.metrics["gauges"]["jit_compiles"] == 0, \
            f"rerun recompiled {second.metrics['gauges']['jit_compiles']} " \
            f"functions (engine={engine})"
        assert _numeric(first) == _numeric(second)


# --------------------------------------------- federation surface ---

class TestFederationSurface:
    def test_obs_attaches_metrics_and_trace_path(self, setup, tmp_path):
        path = str(tmp_path / "fed.jsonl")
        res = _run(setup, "vafl", "event",
                   obs=ObsConfig(trace_jsonl=path))
        assert res.trace_path == path and os.path.exists(path)
        assert set(res.metrics) == {"counters", "gauges", "histograms"}
        assert "jit_compiles" in res.metrics["gauges"]

    def test_obs_off_leaves_result_untouched(self, setup):
        res = _run(setup, "vafl", "event")
        assert res.metrics is None and res.trace_path is None

    def test_to_summary_keys(self, setup):
        s = _run(setup, "vafl", "event").to_summary()
        for k in ("algorithm", "best_acc", "uploads", "scalar_reports",
                  "broadcasts", "uplink_mb", "downlink_mb",
                  "total_wire_mb", "byte_ccr", "uploads_to_target",
                  "time_to_target", "sim_time", "trace_path"):
            assert k in s, k
        assert s["algorithm"] == "vafl"
        assert s["uploads"] > 0

    def test_trace_header_metadata(self, setup, tmp_path):
        _, header, _ = _traced(setup, "vafl", dict(mode="event"),
                               tmp_path, "hdr")
        assert header["schema"] == "obs-trace/v1"
        assert header["meta"]["algorithm"] == "vafl"
        assert header["meta"]["num_clients"] == N


# ------------------------------------------------------ unit layer ---

class TestMetricsRegistry:
    def test_kind_conflict_is_loud(self):
        reg = MetricsRegistry()
        reg.counter("x").inc()
        with pytest.raises(TypeError, match="already exists"):
            reg.gauge("x")

    def test_pow2_buckets(self):
        h = Histogram()
        for v in (0, 1, 2, 3, 4, 5, 1000):
            h.observe(v)
        # bucket k counts (2^(k-1), 2^k]: 0,1 -> k=0; 2 -> 1; 3,4 -> 2;
        # 5 -> 3; 1000 -> 10
        assert h.buckets == {0: 2, 1: 1, 2: 2, 3: 1, 10: 1}
        assert h.count == 7 and h.min == 0 and h.max == 1000

    def test_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("c").inc(3)
        reg.gauge("g").set(1.5)
        reg.hist("h").observe(2)
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 3
        assert snap["gauges"]["g"] == 1.5
        assert snap["histograms"]["h"]["count"] == 1
        json.dumps(snap)  # JSON-ready


class TestTracerAndExporters:
    def test_max_events_counts_drops(self):
        t = Tracer(max_events=2)
        for i in range(5):
            t.emit("e", "i", sim=float(i))
        assert len(t.events) == 2 and t.dropped == 3

    def test_chrome_trace_dual_timeline(self, tmp_path):
        obs = Observer(ObsConfig(), {"algorithm": "t"})
        obs.upload(0, 1.0, nbytes=10)           # sim-timeline instant
        with obs.timed("encode"):               # host-only span
            pass
        obs.window(2, 0.0, 1.0, obs.host_now()) # both timelines
        path = str(tmp_path / "chrome.json")
        write_chrome_trace(obs.tracer, path, obs.meta)
        with open(path) as f:
            doc = json.load(f)
        pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] != "M"}
        assert pids == {1, 2}  # sim clock + host clock
        # the window span appears on BOTH timelines
        wins = [e for e in doc["traceEvents"] if e.get("name") == "window"]
        assert {e["pid"] for e in wins} == {1, 2}

    def test_console_summary(self, setup):
        res = _run(setup, "vafl", "event", obs=True)
        obs = Observer(ObsConfig(), {"algorithm": "vafl"})
        obs.upload(0, 1.0, nbytes=8)
        text = console_summary(obs, res)
        assert "upload" in text and "vafl" in text

    def test_jsonl_roundtrip(self, tmp_path):
        from repro.obs.exporters import write_jsonl
        t = Tracer()
        t.event("upload", 1.5, 2, nbytes=64)
        t.span("window", 0.0, 2.0, 0.0, size=4)
        path = write_jsonl(t, str(tmp_path / "t.jsonl"), {"m": 1})
        header, events = read_jsonl(path)
        assert header["events"] == 2 and header["meta"] == {"m": 1}
        assert events[0]["name"] == "upload"
        assert events[0]["nbytes"] == 64
        assert events[1]["sim_dur"] == 2.0


class TestConfig:
    def test_resolve_variants(self):
        assert resolve_obs(None) is None
        assert resolve_obs(False) is None
        assert isinstance(resolve_obs(True), ObsConfig)
        cfg = ObsConfig(summary=True)
        assert resolve_obs(cfg) is cfg
        assert resolve_obs({"max_events": 7}).max_events == 7
        with pytest.raises(ValueError, match="obs must be"):
            resolve_obs("yes")

    def test_compile_tracking_installed(self):
        from repro.obs import compile_count, install
        install()
        install()  # idempotent
        assert compile_count() >= 0


class TestCompileStats:
    def test_a_miss_then_a_hit(self, tmp_path):
        """The backend step of one new jit is a persistent-cache miss
        (compiled and written) in a fresh cache, and a hit (loaded) once
        the in-memory caches are cleared; both count as backend steps."""
        import jax
        import jax.numpy as jnp
        from jax.experimental.compilation_cache import compilation_cache
        from repro.obs import compile_stats, install

        install()
        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")
        old = {k: getattr(jax.config, k) for k in keys}
        x = jnp.arange(5.0)
        f = jax.jit(lambda v: v * 3.0 + 1.0)
        try:
            jax.config.update(keys[0], str(tmp_path))
            jax.config.update(keys[1], 0.0)
            jax.config.update(keys[2], 0)
            compilation_cache.reset_cache()
            s0 = compile_stats()
            f(x).block_until_ready()
            s1 = compile_stats()
            jax.clear_caches()
            f(x).block_until_ready()
            s2 = compile_stats()
        finally:
            for k, v in old.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()

        def step(a, b):
            return {k: b[k] - a[k] for k in ("backend_count", "cache_hits",
                                             "cache_misses")}
        assert step(s0, s1) == {"backend_count": 1, "cache_hits": 0,
                                "cache_misses": 1}
        assert step(s1, s2) == {"backend_count": 1, "cache_hits": 1,
                                "cache_misses": 0}
        assert s2["cache_retrieval_s"] > s1["cache_retrieval_s"]
        for k in ("trace", "lower", "backend"):
            assert s2[f"{k}_count"] >= s0[f"{k}_count"] + 1
            assert s2[f"{k}_s"] > s0[f"{k}_s"]

    def test_traced_run_records_its_compiles(self, setup, tmp_path):
        """A traced run's backend steps become ``compile`` records naming
        the program, as many as the ``jit_compiles`` gauge counts."""
        import jax

        jax.clear_caches()
        res, _, events = _traced(
            setup, "vafl", dict(mode="event", engine="batched",
                                max_batch=3), tmp_path, "compiles", rounds=1)
        compiles = [e for e in events if e["name"] == "compile"]
        assert len(compiles) == res.metrics["gauges"]["jit_compiles"] > 0
        assert "jit(update)" in {e["fun_name"] for e in compiles}
        assert all(isinstance(e["cache_hit"], bool) and e["secs"] >= 0
                   for e in compiles)

    def test_traced_run_opens_with_the_compile_totals(self, setup, tmp_path):
        """A traced run's first record holds the process's compile and
        cache totals as the run started: what came before it."""
        from repro.obs import compile_stats, install

        install()
        before = compile_stats()
        _, _, events = _traced(
            setup, "vafl", dict(mode="event", engine="batched",
                                max_batch=3), tmp_path, "totals", rounds=1)
        after = compile_stats()
        totals = [e for e in events if e["name"] == "compile_totals"]
        assert len(totals) == 1 and events[0] is totals[0]
        rec = totals[0]
        assert set(before) <= set(rec)
        for k in before:
            assert before[k] <= rec[k] <= after[k], k
