"""The control, the reference computed one precision below the
configuration's and put in the program's place, has to fail each cell's
comparison with the limits the cell runs with.

On the chip the control is ``Precision.HIGH``; on the CPU, which computes
every float32 product exactly, the three bfloat16 passes are spelled out
(``reference.three_pass``).  The local round here is 20 SGD steps of two
clients, so that rounding has room to grow as it does over the cell's
1335 steps."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchsmall import SEED, SMALL  # noqa: E402

CONTROL = dict(SMALL, num_clients=2, buffer_size=1,
               local=dict(SMALL["local"], local_rounds=20),
               data=dict(SMALL["data"], train_samples=64),
               reference=dict(SMALL["reference"], clients_per_step=2,
                              eval_clients_per_step=2))


@pytest.mark.parametrize("workload", ["paper7_vafl_topk"])
def test_control_fails_the_comparison(workload):
    from bench import compare, datagen, harness, reference
    spec = harness.load(workload, config_override=CONTROL)
    cfg, traffic = spec["config"], spec["traffic"]
    data = datagen.make(SEED, cfg["data"], cfg["num_clients"])
    upd = reference.updates(cfg, traffic, data, SEED)
    control = reference.commits(cfg, traffic, reference.updates(
        cfg, traffic, data, SEED, **reference.control_kw(cfg)), SEED)
    ref = reference.commits(
        cfg, traffic, upd, SEED,
        follow=control["decisions"] if traffic["algorithm"] == "vafl"
        else None, tie_margin=spec["cell_file"]["tie_margin"])
    ok, rows = compare.judge(compare.numbers(control, ref),
                             spec["cell_file"]["limits"])
    assert not ok, rows
