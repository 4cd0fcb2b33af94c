"""Quickstart: the VAFL public API in ~20 lines.

    PYTHONPATH=src python examples/quickstart.py

Builds a 3-client federation on synthetic MNIST, runs 8 wall-clock
rounds of VAFL on the paper's simulated testbed (``repro.sim`` scenario:
laptop + Pi devices on a home LAN, byte-aware link delays), and prints
the communication ledger — the scalar V reports that replace most
full-model uploads — plus the simulated time-to-accuracy the scenario
subsystem adds.  Swap ``algorithm=`` for any registered name ("afl",
"eaflm", "fedavg", "fedasync", ...; see repro.algorithms and
docs/ARCHITECTURE.md) and ``scenario=`` for any zoo name
("mobile_fleet", "flaky_edge", "datacenter", ...; see docs/SCENARIOS.md)
— the runtimes are algorithm- and scenario-agnostic.
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.common.compile_cache import enable_compile_cache
from repro.core import Federation
from repro.core.client import LocalSpec
from repro.core.metrics import ccr
from repro.data.partition import iid_partition
from repro.data.synthetic import synthetic_mnist

enable_compile_cache()

# 1. data: synthetic MNIST, split IID across 3 clients
xtr, ytr, xte, yte = synthetic_mnist(3000, 1000, seed=0)
fed_data = iid_partition(xtr, ytr, num_clients=3, samples_per_client=1000)

# 2. the federation: model + algorithm + codecs + scenario in one object
#    (any (forward_fn, init_fn, cfg) pytree model plugs in the same way)
fed = Federation(model="mlp", data=fed_data, test_data=(xte, yte),
                 algorithm="vafl", scenario="paper_testbed",
                 local=LocalSpec(batch_size=32, local_epochs=1,
                                 local_rounds=1, lr=0.1),
                 target_acc=0.85)

# 3. VAFL: every completion the client reports the scalar V_i (Eq. 1);
#    only above-mean clients upload their model (Eq. 2)
res = fed.run(rounds=8, mode="event", verbose=True)

print(f"\nbest Acc          : {res.best_acc:.4f}")
print(f"model uploads     : {res.comm.model_uploads} "
      f"(plain AFL would use {8 * 3})")
print(f"scalar V reports  : {res.comm.scalar_reports} "
      f"({res.comm.scalar_reports * 4} bytes total)")
print(f"CCR vs AFL        : {ccr(8 * 3, res.comm.model_uploads):.2%}")
print(f"sim wall-clock    : {res.sim_time:.1f} s "
      f"(mean idle {res.idle_fraction:.1%})")
print(f"bytes on the wire : {res.comm.uplink_bytes / 1e6:.2f} MB up / "
      f"{res.comm.downlink_bytes / 1e6:.2f} MB down")
tta = ("not reached" if res.time_to_target is None
       else f"{res.time_to_target:.1f} s simulated")
print(f"time to {fed.config.target_acc:.0%} Acc   : {tta}")
