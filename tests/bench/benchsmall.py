"""A cell cut to a size the CPU test run can hold: the published CNN at full
width, four clients of 32 samples, two SGD steps a round, 50 test images,
every upload mixed on arrival (a buffer of one), as in the paper's setup."""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SMALL = {"num_clients": 4, "buffer_size": 1,
         "local": {"batch_size": 32, "local_epochs": 1, "local_rounds": 2,
                   "lr": 0.1},
         "data": {"partition": "iid", "train_samples": 128,
                  "test_samples": 50},
         "reference": {"clients_per_step": 4, "eval_clients_per_step": 4,
                       "test_block": 50}}

SEED = 2**31 + 11


def run_small(workload: str, traced: bool = False, seed: int = SEED):
    """One run of ``workload`` at the small size on the CPU, past the
    harness's look for a chip."""
    from bench import harness
    return harness.run(workload, seed, 1.0, traced, time.perf_counter(),
                       require_chip=False, cache=False,
                       config_override=SMALL)


def small_spec(workload: str) -> dict:
    from bench import harness
    return harness.load(workload, config_override=SMALL)
