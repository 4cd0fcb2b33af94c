"""The program's host spans on the profiler's clock: a batched run's spans
read back from a profile, the device's idle time put down to them on a
hand-built trace with a known answer (``bench/host_spans.py``), and the
readers of the metrics that read the program's spans and compile totals."""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, host_spans  # noqa: E402


def _inside(inner, outer) -> bool:
    return (outer[1] <= inner[1]
            and inner[1] + inner[2] <= outer[1] + outer[2])


def test_spans_of_a_batched_run_nest_in_the_profile(tmp_path):
    """A small batched VAFL + topk0.1_int8 run with obs on, profiled: its
    spans come back from the host plane, nested as the engine opens them,
    one ``upload_path`` per accepted upload and one ``window`` per
    window."""
    import jax
    from repro.core import FLRunConfig, run_event_driven
    from repro.core.client import (LocalSpec, make_evaluator,
                                   make_weighted_classifier_loss)
    from repro.data.partition import iid_partition
    from repro.data.synthetic import synthetic_mnist
    from repro.models.cnn import MLPConfig, mlp_forward, mlp_init

    n = 4
    xtr, ytr, xte, yte = synthetic_mnist(n * 64 + 100, 100, seed=1)
    mcfg = MLPConfig(hidden=(16,))
    rc = FLRunConfig(algorithm="vafl", num_clients=n, rounds=3,
                     local=LocalSpec(batch_size=32, local_rounds=1, lr=0.1),
                     engine="batched", compressor="topk0.1_int8",
                     events_per_eval=n, obs=True, seed=3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = run_event_driven(
            rc, init_params_fn=lambda k: mlp_init(mcfg, k),
            loss_fn=make_weighted_classifier_loss(mlp_forward, mcfg),
            fed_data=iid_partition(xtr, ytr, n, samples_per_client=64,
                                   seed=1),
            evaluate_fn=make_evaluator(mlp_forward, mcfg, xte, yte,
                                       batch=100))
    finally:
        jax.profiler.stop_trace()
    spans = host_spans.read(str(tmp_path))
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    assert {"run.start", "window", "window.dispatch", "window.pipeline",
            "window.wait", "window.decide", "upload_path", "encode",
            "window.commit", "eval", "run.finish"} <= set(by)
    assert len(by["window"]) == res.metrics["counters"]["windows"] == 3
    assert len(by["upload_path"]) == res.comm.model_uploads > 0
    assert len(by["eval"]) == len(res.records)
    for up in by["upload_path"]:
        decide = [d for d in by["window.decide"] if _inside(up, d)]
        assert len(decide) == 1
        assert any(_inside(decide[0], w) for w in by["window"])
        assert int(up[3]["client"]) in range(n)
    for enc in by["encode"]:
        assert any(_inside(enc, up) for up in by["upload_path"])
    run = host_spans.run_window(spans)
    assert run is not None
    assert all(run[0] <= sp[1] and sp[1] + sp[2] <= run[1]
               for sp in spans if sp[0] != "compile")


# one device; times in ns.  Busy: [0, 50], [120, 380], [620, 640] and
# [960, 1050].  Spans: the run from 0 to 1100, a window over [100, 940],
# its decision loop over [400, 900] with one upload path [500, 700] and
# its encode [550, 600]; nothing is open over [940, 1000].
DEVICES = {"/device:TPU:0": {
    "modules": [["jit_a(1)", 0, 50], ["jit_b(2)", 120, 260],
                ["jit_c(3)", 620, 20], ["jit_d(4)", 960, 90]],
    "ops": []}}
SPANS = [["run.start", 0, 100, {}], ["window", 100, 840, {"size": 4}],
         ["window.decide", 400, 500, {}], ["upload_path", 500, 200, {}],
         ["encode", 550, 50, {}], ["run.finish", 1000, 100, {}]]


@pytest.fixture(scope="module")
def attributed():
    return host_spans.attribute(DEVICES, SPANS)


def test_idle_goes_to_the_innermost_open_span(attributed):
    ns = 1e-9
    want = {"run.start": 50, "window": 20 + 20 + 40,
            "window.decide": 100 + 200, "upload_path": 50 + 20 + 60,
            "encode": 50, "run.finish": 50, "unattributed": 20}
    got = attributed["idle_by_span"]
    assert set(got) == set(want)
    for name, v in want.items():
        assert got[name] == pytest.approx(v * ns, abs=1e-15), name
    # the whole stretch from run start to finish, less what was busy
    assert sum(got.values()) == pytest.approx((1100 - 50 - 260 - 20 - 90)
                                              * ns)
    assert attributed["span_window_s"] == pytest.approx(1100 * ns)


def test_idle_within_a_span_holds_the_spans_inside_it(attributed):
    within = attributed["idle_within"]
    assert within["upload_path"] == pytest.approx(180e-9)
    assert within["window.decide"] == pytest.approx(480e-9)
    assert "unattributed" not in within


@pytest.mark.parametrize("gap,want", [
    # [640, 960]: 200 ns in the decision loop, 60 in the upload path, 40
    # in the window and 20 under no span
    ((640, 960), "window.decide"),
    # [380, 620]: 100 in the decision loop, 70 in the upload path, 50 in
    # its encode, 20 in the window
    ((380, 620), "window.decide"),
    ((50, 120), "run.start"),
    ((940, 1000), None)])
def test_a_gap_carries_the_span_that_held_most_of_it(gap, want):
    assert host_spans.gap_span(host_spans.segments(SPANS), *gap) == want


def test_a_trace_without_a_whole_run_attributes_nothing():
    r = host_spans.attribute(DEVICES, SPANS[1:])
    assert r == {"idle_by_span": {}, "idle_within": {}, "span_window_s": 0.0}
    assert host_spans.attribute({}, SPANS)["idle_by_span"] == {}


NEW_METRICS = ("upload_path_ms_per_upload", "setup_compile_s")
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12,
         "hbm_bytes_per_s": 819e9}
# the program's trace records, as the closed-loop driver hands them on
RECORDS = [
    {"name": "compile_totals", "ph": "i", "host": 0.0, "trace_s": 1.5,
     "lower_s": 0.25, "backend_s": 4.0, "cache_hits": 9, "cache_misses": 0,
     "cache_retrieval_s": 0.5},
    {"name": "upload_path", "ph": "X", "host": 0.5, "host_dur": 0.125,
     "client": 1},
    {"name": "encode", "ph": "X", "host": 0.5, "host_dur": 0.05,
     "client": 1},
    {"name": "upload_path", "ph": "X", "host": 1.0, "host_dur": 0.075,
     "client": 3}]


def _ctx(spans, peaks=PEAKS):
    return SimpleNamespace(driver="closed_loop", events=8, uploads=2,
                           window_s=2.0, spans=spans, peaks=peaks)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_without_spans_or_counters(name):
    assert harness.read_metric(name, _ctx([])) is None
    others = [e for e in RECORDS
              if e["name"] not in ("upload_path", "compile_totals")]
    assert harness.read_metric(name, _ctx(others)) is None


def test_readers_read_the_spans_and_counters():
    got = {m: harness.read_metric(m, _ctx(RECORDS)) for m in NEW_METRICS}
    assert got["upload_path_ms_per_upload"] == pytest.approx(100.0)
    assert got["setup_compile_s"] == pytest.approx(5.75)
    # host times and compile seconds beside a CPU backend are not read
    for m in NEW_METRICS:
        assert harness.read_metric(m, _ctx(RECORDS, peaks=None)) is None
