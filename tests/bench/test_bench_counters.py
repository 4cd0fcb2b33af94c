"""The benchmark's yardstick pieces that need no chip: operation and byte
counts against hand counts, the data generator, and the contract of
BENCHMARK.json."""
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import datagen, flops  # noqa: E402

sys.path.insert(0, os.path.join(ROOT, "src"))

CNN = {"image_size": 28, "channels": [16, 32], "num_blocks": 2,
       "num_classes": 10}


def test_cnn_forward_flops_match_hand_count():
    # 2*H*W*k^2*cin*cout over the ten convolutions, plus the head:
    # stem 225,792; stage one 4 x 3,612,672; stage two c1 1,806,336,
    # c2 3,612,672, proj 200,704, second block 2 x 3,612,672; head 640
    assert flops.cnn_forward_flops(CNN) == 27_522_176
    assert len(flops.cnn_layers(CNN)) == 10


def test_cnn_params_match_the_model():
    assert flops.cnn_params(CNN) == 42_698


def test_topk_quant_bytes_at_its_cnn_shape():
    rows = flops.topk_quant_rows(42_698)
    assert rows == 512
    # float32 in, int8 values and mask out, three 4-byte scalars
    assert flops.topk_quant_bytes(rows) == 512 * 128 * 6 + 12 == 393_228


@pytest.mark.parametrize("clients,steps", [(256, 35), (7, 1335)])
def test_steps_per_event(clients, steps):
    cfg = {"local": {"batch_size": 32, "local_epochs": 1,
                     "local_rounds": 5},
           "data": {"train_samples": 60000}, "num_clients": clients}
    assert flops.steps_per_event(cfg) == steps


def test_data_is_a_function_of_the_seed():
    """The training data follow the seed; the test set is one fixed set."""
    cfg = {"partition": "iid", "train_samples": 64, "test_samples": 16}
    a = datagen.make(2**31 + 5, cfg, 4)
    b = datagen.make(2**31 + 5, cfg, 4)
    c = datagen.make(2**31 + 6, cfg, 4)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["images"], c["images"])
    np.testing.assert_array_equal(a["test_images"], c["test_images"])
    np.testing.assert_array_equal(a["test_labels"], c["test_labels"])
    assert a["images"].shape == (4, 16, 28, 28)
    assert a["images"].dtype == np.float32


def test_shifted_prototypes_are_rolls():
    protos = datagen.prototypes()
    table = datagen._shifted_table(protos)
    np.testing.assert_array_equal(
        table[3, 0, 6], np.roll(np.roll(protos[3], -3, 0), 3, 1))


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_follows_its_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bm["configs"]}
    cells = {w["name"]: w for w in bm["workloads"]}
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for c in bm["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in bm["workloads"])

    def reports(cell, metric):
        return cell in metric.get("workloads", cells)

    for name, w in cells.items():
        assert sum(reports(name, m) for m in bm["end_to_end"]) >= 2
        assert NAME.match(name) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            ROOT, "bench", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "bench", "cells", name + ".json"))
        layer = [m for m in bm["per_layer"] if name in m["workloads"]]
        assert layer, f"{name} reports no per-layer metric"
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(cells)
        assert all(reports(c, e2e[m["moves"]]) for c in m["workloads"])
        assert os.path.exists(os.path.join(
            ROOT, "bench", "metrics", m["name"] + ".py"))


def test_norm_gap_treats_nan_on_both_sides_as_agreement():
    from bench import compare
    nan = float("nan")
    a = {"w": np.array([1.0, 2.0]), "b": np.array([nan])}
    assert compare.norm_gap(a, {"w": np.array([1.0, 2.0]),
                                "b": np.array([nan])}) == 0.0
    assert compare.norm_gap(a, {"w": np.array([1.0, 2.0]),
                                "b": np.array([0.5])}) == float("inf")


def test_reference_gate_matches_the_program_on_nan_reports():
    """A diverged round reports NaN; the gate's mean runs over finite
    reports, and with none yet the report uploads."""
    import jax
    from bench import reference
    from repro.algorithms.builtin import VAFLPolicy
    values = np.array([np.nan, 1.0, 3.0, 0.5, 2.0])
    policy = VAFLPolicy(None)
    policy.begin_run(len(values))
    want = [policy.decide(i, v, None, 0.0) for i, v in enumerate(values)]
    n = len(values)
    g0 = {"w": jax.numpy.zeros(3)}
    upd = {"g0": g0, "order": np.arange(n), "values": values,
           "new": {"w": jax.numpy.ones((n, 3))},
           "eff": {"w": np.zeros((n, 3))}}
    got = reference.commits({"num_clients": n, "buffer_size": 1},
                            {"algorithm": "vafl", "compressor": "identity"},
                            upd, seed=0)["decisions"]
    assert [got[i] for i in range(n)] == want


def test_end_state_mismatches_count_each_differing_entry():
    from bench import closed_loop
    nan = float("nan")
    a = [("global_acc", 0.1), ("values", [1.0, nan]), ("uploads", 3)]
    assert closed_loop.mismatches(a, list(a)) == 0
    b = [("global_acc", 0.1), ("values", [1.0, 2.0]), ("uploads", 4)]
    assert closed_loop.mismatches(a, b) == 2
    assert closed_loop.mismatches(a, a[:2]) == 1
