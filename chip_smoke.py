#!/usr/bin/env python3
"""Chip smoke test: the federated-learning main path on a TPU, end to end.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # client state sharded over four chips

The default run has four phases, in one process:

1. Device check.  A TPU or a non-zero exit: there is no CPU fallback.
2. Kernels.  ``topk_quant`` and ``grad_diff_norm`` compiled for the chip
   (``tpu_custom_call`` in the compiled program) and checked against
   their oracles: bit-equal planes, and the squared norm within rtol
   1e-5.
3. Closed loop.  ``Federation(model="cnn")`` at the full width of
   ``CNNConfig()``, synthetic MNIST (60000/10000) split IID over N=256
   clients, batched VAFL with the ``topk0.1_int8`` codec and a FedBuff
   buffer of 8, run for 2N events.  The final global model comes back
   through the run's checkpoint; its test loss must be finite and below
   the initial model's, and its accuracy above chance.
4. Served path.  The same federation behind ``Federation.serve`` with
   thread clients over the in-process transport.

``--chips 4`` runs only the closed loop with ``shard_clients=True`` over
the four chips, and the same run unsharded on one chip, and compares
them.  Every phase prints its numbers on a line of its own (wall times
include compilation).  A phase that raises or fails a check ends the
script with a non-zero exit before the last line, which is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_CLIENTS = 256
N_TRAIN, N_TEST = 60000, 10000


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _versions() -> dict:
    from importlib import metadata
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = "not installed"
    return out


# ------------------------------------------------------------- kernels ---

def kernel_phase() -> None:
    """Both main-path kernels compiled for the chip vs their oracles, at
    the CNN's padded flat width."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.common.pytree import tree_sq_diff_norm
    from repro.compress.sparsify import flatten_tree
    from repro.kernels.grad_diff_norm import ops as gd_ops
    from repro.kernels.topk_quant import ops as tq_ops, ref as tq_ref
    from repro.kernels.topk_quant.kernel import topk_quant_2d
    from repro.models.cnn import CNNConfig, cnn_init

    cfg = CNNConfig()
    pa = cnn_init(cfg, jax.random.key(1))
    pb = cnn_init(cfg, jax.random.key(2))
    flat = flatten_tree(pa)[0]
    n = int(flat.shape[0])
    x2d = tq_ops.pad_2d(flat)
    thr, scale = tq_ops.topk_threshold_scale(x2d, n, max(1, round(0.1 * n)))
    seed = jnp.uint32(0x9E3779B9)

    compiled = topk_quant_2d.lower(x2d, thr, scale, seed).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "topk_quant: no tpu_custom_call in the compiled program")
    q, mask = compiled(x2d, thr, scale, seed)
    q_ref, mask_ref = tq_ref.topk_quant_2d(x2d, thr, scale, seed)
    q, mask, q_ref, mask_ref = map(np.asarray, (q, mask, q_ref, mask_ref))
    q_bad = int(np.sum(q != q_ref))
    mask_bad = int(np.sum(mask != mask_ref))
    log("topk_quant", params=n, shape=tuple(x2d.shape),
        kept=int(mask.sum()), tpu_custom_call=True,
        q_mismatches=q_bad, mask_mismatches=mask_bad)
    check(q_bad == 0 and mask_bad == 0,
          "topk_quant: compiled kernel and oracle differ")

    compiled = gd_ops.tree_grad_diff_sq_norm.lower(pa, pb).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "grad_diff_norm: no tpu_custom_call in the compiled program")
    got = float(compiled(pa, pb))
    want = float(tree_sq_diff_norm(pa, pb))
    rel = abs(got - want) / abs(want)
    log("grad_diff_norm", params=n, kernel=got, oracle=want, rel_err=rel,
        tpu_custom_call=True)
    check(rel <= 1e-5, f"grad_diff_norm: rel err {rel} > 1e-5")


# --------------------------------------------------------- closed loop ---

def build_federation(n_clients: int = N_CLIENTS, n_train: int = N_TRAIN,
                     n_test: int = N_TEST, **federation_kw):
    """VAFL + topk0.1_int8 on the batched engine, full-width CNN, IID
    synthetic MNIST (n_train / n_clients samples per client), every
    other ``Federation`` option at its default."""
    from repro.core import Federation
    from repro.data.partition import iid_partition
    from repro.data.synthetic import synthetic_mnist

    xtr, ytr, xte, yte = synthetic_mnist(n_train, n_test, seed=0)
    data = iid_partition(xtr, ytr, n_clients, seed=0)
    fed = Federation(model="cnn", data=data, test_data=(xte, yte),
                     algorithm="vafl", compressor="topk0.1_int8",
                     engine="batched", buffer_size=8, **federation_kw)
    return fed, (xte, yte)


def _test_losses(fed, cfg, path, test):
    """Test loss of the initial global model and of the global model in
    the run's last checkpoint, and the event count that checkpoint was
    taken at."""
    import jax
    import repro.checkpoint.store as ck
    # the engine's own init: split(key(seed)) -> (rng, init key)
    init = fed.init_params_fn(jax.random.split(jax.random.key(cfg.seed))[1])
    state = ck.load_run_state(path, ck.run_fingerprint(cfg, "batched", init))
    xte, yte = test
    batch = {"images": xte, "labels": yte}
    loss = jax.jit(lambda p: fed.loss_fn(p, batch)[0])
    return (float(loss(init)), float(loss(state["global_params"])),
            int(state["event"]))


def closed_loop(fed, test, phase: str = "closed_loop", **overrides):
    """One ``run(mode="event")`` of 2N events, checked; returns the
    RunResult and its record accuracies."""
    import numpy as np
    from repro.obs import compile_count

    n = fed.config.num_clients
    with tempfile.TemporaryDirectory() as tmp:
        overrides.update(rounds=2, checkpoint_path=os.path.join(tmp, "run.ckpt"),
                         checkpoint_every=2 * n)
        c0, t0 = compile_count(), time.perf_counter()
        res = fed.run(mode="event", **overrides)
        wall = time.perf_counter() - t0
        cfg = dataclasses.replace(fed.config, **overrides)
        loss0, loss, events = _test_losses(fed, cfg,
                                           overrides["checkpoint_path"], test)
    accs = [r.global_acc for r in res.records]
    log(phase, clients=n, committed_events=events,
        model_uploads=res.comm.model_uploads,
        scalar_reports=res.comm.scalar_reports,
        byte_ccr=res.byte_ccr, global_acc=accs[-1] if accs else None,
        best_acc=res.best_acc, init_test_loss=loss0, test_loss=loss,
        jit_compiles=compile_count() - c0, wall_s=wall)
    check(events == 2 * n, f"{phase}: {events} events committed, "
                           f"expected {2 * n}")
    check(bool(accs) and all(math.isfinite(a) for a in accs),
          f"{phase}: non-finite or missing accuracy records {accs}")
    check(math.isfinite(loss), f"{phase}: test loss {loss} is not finite")
    check(loss < loss0, f"{phase}: test loss {loss} is not below the "
                        f"initial model's {loss0}")
    check(res.best_acc > 0.10, f"{phase}: accuracy {res.best_acc} is not "
                               "above chance (0.10)")
    check(res.byte_ccr > 0, f"{phase}: byte_ccr {res.byte_ccr} is not > 0")
    check(res.comm.model_uploads > 0, f"{phase}: no model uploads")
    return res, np.asarray(accs)


def served_phase(fed) -> None:
    """The federation as a live service: one thread client per client,
    in-process transport, one round of uploads."""
    from repro.obs import compile_count
    c0, t0 = compile_count(), time.perf_counter()
    res = fed.serve(rounds=1, driver="thread", transport="inproc", obs=True)
    wall = time.perf_counter() - t0
    counters = res.metrics["counters"]
    commits = counters.get("flushes", 0)
    accs = [r.global_acc for r in res.records]
    log("served", clients=fed.config.num_clients,
        model_uploads=res.comm.model_uploads,
        scalar_reports=res.comm.scalar_reports, commits=commits,
        uploads_per_s=res.comm.model_uploads / wall,
        global_acc=accs[-1] if accs else None,
        jit_compiles=compile_count() - c0, wall_s=wall)
    check(res.comm.model_uploads > 0, "served: no uploads answered")
    check(commits > 0, "served: no commits")
    check(all(math.isfinite(a) for a in accs),
          f"served: non-finite accuracy records {accs}")


# ------------------------------------------------------ four-chip path ---

@contextlib.contextmanager
def _record_client_placement(out: list):
    """Record, for each leaf the batched engine places on its client
    sharding (the engine's ``tree_shard`` calls), the number of devices
    it spans and how many of its rows one device holds."""
    import jax
    import repro.core.runtimes.batched as batched
    real = batched.tree_shard

    def spy(tree, sharding):
        placed = real(tree, sharding)
        out.extend((len(leaf.sharding.device_set),
                    leaf.addressable_shards[0].data.shape[0], leaf.shape[0])
                   for leaf in jax.tree.leaves(placed))
        return placed

    batched.tree_shard = spy
    try:
        yield out
    finally:
        batched.tree_shard = real


@contextlib.contextmanager
def _record_gate(out: list):
    """Record each VAFL upload decision: (reported value, uploaded)."""
    from repro.algorithms.builtin import VAFLPolicy
    real = VAFLPolicy.decide

    def spy(self, i, value, norm, threshold):
        up = real(self, i, value, norm, threshold)
        out.append((value, up))
        return up

    VAFLPolicy.decide = spy
    try:
        yield out
    finally:
        VAFLPolicy.decide = real


def _gate_diff(a: list, b: list) -> dict:
    """Where two runs' upload decisions first part, and how far their
    reported values were apart up to there."""
    import numpy as np
    n = min(len(a), len(b))
    va, vb = (np.array([v for v, _ in g[:n]], np.float64) for g in (a, b))
    flips = np.flatnonzero([a[j][1] != b[j][1] for j in range(n)])
    first = int(flips[0]) if len(flips) else n
    rel = np.abs(va[:first] - vb[:first]) / np.abs(va[:first])
    return {"events": n, "first_flip": first if len(flips) else None,
            "values_differing": int(np.sum(va[:first] != vb[:first])),
            "max_rel_value_diff": float(rel.max()) if first else 0.0}


def four_chip_phase(n_chips: int, fed=None, test=None) -> None:
    """The closed loop with client state sharded over ``n_chips`` chips
    vs the same run unsharded on one chip: equal uploads, and record
    accuracies within 1e-3.

    On the CPU backend the two runs are bit-identical
    (tests/test_async_engine.py).  On a TPU they are not: the compiler
    builds other programs for N / n_chips clients per chip than for N
    clients on one chip, a client's update differs in its low bits at
    any matmul precision, and VAFL's above-mean gate can turn a near-tie
    into the other decision (docs/ASYNC_ENGINE.md, "Sharding").  The
    ``shard_compare`` line says where the two runs' decisions first part
    and how far their reported values were apart up to there."""
    import numpy as np
    if fed is None:
        fed, test = build_federation()
    with _record_client_placement([]) as spans, _record_gate([]) as gate_sh:
        sharded, acc_sh = closed_loop(fed, test, phase="sharded",
                                      shard_clients=True)
    with _record_gate([]) as gate_ref:
        ref, acc_ref = closed_loop(fed, test, phase="one_chip",
                                   shard_clients=False)
    diff = (float(np.max(np.abs(acc_sh - acc_ref)))
            if acc_sh.shape == acc_ref.shape else None)
    log("shard_compare", chips=n_chips, placed_leaves=len(spans),
        leaf_device_spans=sorted({d for d, _, _ in spans}),
        rows_per_device=sorted({(r, n) for _, r, n in spans}),
        uploads_sharded=sharded.comm.model_uploads,
        uploads_one_chip=ref.comm.model_uploads, max_acc_diff=diff,
        **_gate_diff(gate_sh, gate_ref))
    check(bool(spans) and all(d == n_chips and r * n_chips == n
                              for d, r, n in spans),
          f"sharded client state is not split over {n_chips} devices: "
          f"(devices, rows per device, rows) {sorted(set(spans))}")
    check(sharded.comm.model_uploads == ref.comm.model_uploads,
          "model_uploads differ between the sharded and one-chip runs")
    check(diff is not None and diff <= 1e-3,
          f"record accuracies differ by {diff} (> 1e-3)")


# ---------------------------------------------------------------- main ---

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded closed loop over four chips "
                         "vs one chip")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX sees only {dev.platform} devices "
              f"({len(devices)}); this script runs on a TPU only",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    log("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), **_versions())

    from repro.common.compile_cache import enable_compile_cache
    from repro.obs import install
    log("compile_cache", dir=enable_compile_cache())
    install()

    if args.chips == 4:
        four_chip_phase(args.chips)
    else:
        kernel_phase()
        fed, test = build_federation()
        closed_loop(fed, test)
        served_phase(fed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
