"""Cross-silo VAFL on a multi-pod mesh — closed loop, then served live.

Each pod (8 placeholder CPU devices) hosts one federated silo.  The
same federation runs three ways:

1. **Closed loop, sharded** — the batched async engine with
   ``shard_clients=True``: the stacked per-silo client state is placed
   on a ``("clients",)`` mesh across the pods, so every silo's params
   live on its own device (docs/ASYNC_ENGINE.md "Sharding" — the
   ROADMAP's shard_clients-on-multi-chip item, here on the placeholder
   mesh).

2. **Served, bridge driver** — federation as a live service
   (repro.serve, docs/SERVING.md): a server hot loop drains a transport
   behind the registry; the sequential driver replays the closed-loop
   chain, so the result is bit-identical to the events engine and
   upload-for-upload identical to the sharded run.

3. **Served, live fleet** — one free-running worker thread per silo,
   real concurrency, obs counters reconciled against CommStats.

    PYTHONPATH=src python examples/multipod_vafl.py \
        [--rounds 3] [--silos 8] [--samples 120]

The explicit gated-collective kernel this example used to hand-roll
lives on in ``repro.distributed.gated`` (tests/test_distributed.py);
the serve + engine layers now cover the cross-pod protocol itself.
"""
import argparse
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--silos", type=int, default=8)
    ap.add_argument("--samples", type=int, default=120)
    args = ap.parse_args()

    import jax
    import numpy as np

    from repro.common.compile_cache import enable_compile_cache
    from repro.core import Federation
    from repro.core.client import LocalSpec
    from repro.data.partition import iid_partition
    from repro.data.synthetic import synthetic_mnist
    from repro.models.cnn import MLPConfig, mlp_forward, mlp_init
    from repro.obs import ObsConfig

    enable_compile_cache()
    print(f"devices: {jax.device_count()} placeholder pods, "
          f"{args.silos} silos")
    xtr, ytr, xte, yte = synthetic_mnist(
        args.silos * args.samples + 400, 400, seed=0)
    fed_data = iid_partition(xtr, ytr, args.silos,
                             samples_per_client=args.samples, seed=0)
    mcfg = MLPConfig(hidden=(32,))
    fed = Federation(model=(mlp_forward, mlp_init, mcfg), data=fed_data,
                     test_data=(xte, yte), algorithm="vafl",
                     compressor="topk0.1_int8",
                     local=LocalSpec(batch_size=32, local_rounds=1, lr=0.1),
                     seed=7)

    sharded = fed.run(args.rounds, mode="event", engine="batched",
                      max_batch=1, buffer_size=1, shard_clients=True)
    bridge = fed.serve(args.rounds, driver="sequential")
    live = fed.serve(args.rounds, obs=ObsConfig())

    rows = [("closed loop (sharded pods)", sharded),
            ("served (bridge driver)", bridge),
            ("served (live fleet)", live)]
    print(f"\n{'lap':>28s} {'events':>7s} {'uploads':>8s} "
          f"{'uplink KB':>10s} {'final acc':>10s}")
    for label, res in rows:
        print(f"{label:>28s} {res.comm.broadcasts:>7d} "
              f"{res.comm.model_uploads:>8d} "
              f"{res.comm.uplink_bytes / 1e3:>10.1f} "
              f"{res.records[-1].global_acc:>10.4f}")

    # the served federation reproduces the sharded closed loop: the
    # bridge driver's decisions are identical, accuracies to fp32 noise
    # (cross-device layout is the only difference — same contract as
    # tests/test_async_engine.py's sharded-parity test)
    assert bridge.comm.model_uploads == sharded.comm.model_uploads
    np.testing.assert_allclose(
        [r.global_acc for r in bridge.records],
        [r.global_acc for r in sharded.records], rtol=0, atol=1e-6)
    c = live.metrics["counters"]
    assert c["uploads"] == live.comm.model_uploads
    assert c["broadcasts"] == live.comm.broadcasts
    print("\nserved == sharded closed loop (uploads identical, acc to "
          "1e-6); live-fleet obs counters reconcile with CommStats")


if __name__ == "__main__":
    main()
