"""The harness end to end on the CPU at a small size: the result line's
schema, and the refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchsmall import ROOT, run_small  # noqa: E402


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper7_vafl_topk",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


@pytest.mark.parametrize("traced", [False, True])
def test_result_line_schema(traced):
    line = run_small("paper7_vafl_topk", traced=traced)
    json.dumps(line)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["attempted"] == 4 and line["failed"] == 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    assert line["checks"]["timed_run_mismatches"]["value"] == 0
    metrics = line["metrics"]
    if traced:
        assert set(line["device"]) >= {"busy_s", "window_s"}
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        # the CPU trace holds no accelerator plane: only the host span
        assert set(metrics) == {"codec_host_ms_per_upload"}
    else:
        assert set(metrics) == {"events_per_s", "setup_s"}
        assert metrics["events_per_s"]["unit"] == "events/s"
        assert metrics["setup_s"]["value"] > 0
