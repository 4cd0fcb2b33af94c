"""The reduction from a device trace to per-layer numbers, on a slice of a
trace recorded on a TPU v5 lite chip (bench/fixtures/trace_slice.json: 148
ms of a 256-client VAFL + top-k window, two uploads through the codec)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

FIXTURE = os.path.join(ROOT, "bench", "fixtures", "trace_slice.json")


@pytest.fixture(scope="module")
def reduced():
    with open(FIXTURE) as f:
        devices = json.load(f)
    layers = trace.load_layers(os.path.join(ROOT, "bench", "layers.json"))
    return trace.reduce(devices, layers)


def test_busy_is_the_union_of_the_operations(reduced):
    # a sweep over the 556 operations' start and end points: 535,661 ns
    assert reduced["devices"] == 1
    assert reduced["busy_s"] == pytest.approx(535_661e-9, rel=1e-12)


def test_programs_are_assigned_to_layers(reduced):
    # jit_topk_threshold_scale and jit_topk_quant_2d, two calls each
    assert reduced["layer_s"] == {"codec": pytest.approx(112_824e-9)}
    assert "jit_squeeze" in reduced["unassigned_s"]


def test_kernel_time_is_its_custom_calls(reduced):
    k = reduced["kernel_s"]["topk_quant"]
    assert k["calls"] == 2
    assert k["s"] == pytest.approx((1585 + 1342) * 1e-9)


def test_idle_gaps_name_the_programs_around_them(reduced):
    name, seconds = reduced["idle_gaps"][0]
    assert name == "jit_topk_quant_2d -> jit_convert_element_type"
    assert seconds == pytest.approx(2_637_456e-9)
    assert len(reduced["idle_gaps"]) == 10
    assert len(reduced["top_ops"]) == 10


def test_union_of_nested_and_disjoint_intervals():
    assert trace._union_ns([(0, 10), (2, 3), (10, 5), (20, 1)]) == 16


def test_no_device_plane_reduces_to_nothing():
    r = trace.reduce({}, {"programs": {}, "kernels": {}})
    assert r["devices"] == 0 and r["busy_s"] == 0.0


def test_extract_reads_a_chip_trace():
    """bench/fixtures/tiny_trace: three calls of the topk_quant kernel and
    of a small reduction, traced on a TPU v5 lite chip."""
    devices = trace.extract(os.path.join(ROOT, "bench", "fixtures",
                                         "tiny_trace"))
    assert list(devices) == ["/device:TPU:0"]
    dev = devices["/device:TPU:0"]
    names = [m[0].split("(")[0] for m in dev["modules"]]
    assert names.count("jit_topk_quant_2d") == 3
    layers = trace.load_layers(os.path.join(ROOT, "bench", "layers.json"))
    r = trace.reduce(devices, layers)
    assert r["kernel_s"]["topk_quant"]["calls"] == 3
    kernel = sum(o[2] for o in dev["ops"] if o[0].startswith("%topk_quant"))
    assert r["kernel_s"]["topk_quant"]["s"] == pytest.approx(kernel * 1e-9)
    assert 0 < r["busy_s"] <= sum(m[2] for m in dev["modules"]) * 1e-9
