"""The closed-loop driver: ``Federation.run(mode="event")`` on the batched
engine, timed over whole engine windows.

One run of a cell:

1. set-up: the data from the seed (on the device), the ``Federation``,
   and the warm-up: the run the clock will time, made once before the
   clock starts, with a run-state checkpoint at the end of its first
   window (what the reference is compared with) from which its later
   windows resume.  Every program the timed run uses is compiled (or
   loaded from the persistent cache) here;
2. the timed run: ``rounds = W`` engine windows of N events each, with W
   the whole number of the cell's nominal windows that fit in
   ``--seconds``, a fixed amount of work for a given ``--seconds``; the
   clock runs from a drained device to a drained device, and
   ``events_per_s`` is every committed event over all of that time.  The
   run's own start (initial model, per-client stacks, scheduler) is
   inside the window: the program has no hook to start a run's windows
   apart from its set-up;
3. with ``--trace 1`` the timed run is profiled (``jax.profiler``, no
   Python tracer) with the program's ``repro.obs`` spans on, and the
   per-layer metrics are read from both;
4. the timed run has to end as the warm-up did (every record, every
   reported value and every byte count the same), so what was timed is
   what was checked; the reference recomputes the first window from the
   seed and the comparison decides ``correct`` (``compare.py``).
"""
from __future__ import annotations

import dataclasses
import os
import pickle
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from bench import compare, datagen, flops, reference, trace


def _drain(jax) -> None:
    """Wait for every computation dispatched so far: the device runs its
    programs in dispatch order, so a new one ends after all of them."""
    jax.block_until_ready(jax.device_put(np.float32(0.0)) + 1)


def _build(cfg: dict, traffic: dict, data: dict, seed: int):
    import jax.numpy as jnp
    from repro.core import Federation
    from repro.core.client import LocalSpec
    from repro.data.partition import FederatedData
    from repro.models.cnn import CNNConfig

    model = CNNConfig()
    want = cfg["model"]
    got = {"image_size": model.image_size, "channels": list(model.channels),
           "num_blocks": model.num_blocks, "num_classes": model.num_classes,
           "param_dtype": model.param_dtype}
    if any(want[k] != v for k, v in got.items()):
        raise ValueError(f"Federation(model='cnn') runs {got}; the "
                         f"configuration states {want}")
    fd = FederatedData(images=jnp.asarray(data["images"]),
                       labels=jnp.asarray(data["labels"]),
                       mask=jnp.asarray(data["mask"]), counts=data["counts"])
    local = cfg["local"]
    return Federation(
        model="cnn", data=fd,
        test_data=(data["test_images"], data["test_labels"]),
        algorithm=traffic["algorithm"], compressor=traffic["compressor"],
        engine="batched", max_batch=traffic["max_batch"],
        buffer_size=cfg["buffer_size"], eval_batch=cfg["eval_batch"],
        local=LocalSpec(batch_size=local["batch_size"],
                        local_epochs=local["local_epochs"],
                        local_rounds=local["local_rounds"], lr=local["lr"]),
        seed=seed), fd


def _read_state(path: str) -> dict:
    """The engine's state as its run-state checkpoint holds it."""
    with open(path, "rb") as f:
        return pickle.load(f)["state"]


def _warm_up(fed, n: int, windows: int):
    """The timed run made once: its first window with a checkpoint at the
    end, then its later windows resumed from that checkpoint.  Returns the
    engine's state after the first window and the whole run's result."""
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "first_window.ckpt")
        res = fed.run(mode="event", rounds=1, checkpoint_path=path,
                      checkpoint_every=n)
        state = _read_state(path)
        if windows > 1:
            res = fed.run(mode="event", rounds=windows, checkpoint_path=path,
                          resume=True)
    return state, res


def _plain(v):
    if v is None or isinstance(v, (bool, str)):
        return v
    a = np.asarray(v, np.float64)
    return a.tolist() if a.ndim else float(a)


def end_state(res) -> list:
    """What a run ends with, as plain numbers: every record (clock, global
    accuracy, uploads, selected clients, reported values, client
    accuracies), the message and byte counts, the bytes of each client."""
    out = [(f.name, _plain(getattr(r, f.name)))
           for r in res.records for f in dataclasses.fields(r)]
    out += sorted((k, _plain(v)) for k, v in res.comm.__dict__.items())
    out.append(("client_uplink_bytes", _plain(res.client_uplink_bytes)))
    return out


def mismatches(a: list, b: list) -> int:
    """Entries in which two ``end_state`` lists differ (NaN equals NaN)."""
    def same(x, y):
        if x[0] != y[0]:
            return False
        x, y = x[1], y[1]
        if isinstance(x, (list, float)) or isinstance(y, (list, float)):
            try:
                return np.array_equal(np.asarray(x, np.float64),
                                      np.asarray(y, np.float64),
                                      equal_nan=True)
            except (TypeError, ValueError):
                return False
        return x == y
    return (sum(not same(x, y) for x, y in zip(a, b))
            + abs(len(a) - len(b)))


def _program_side(fed, state: dict, traffic: dict, seed: int):
    """The program's first window in the comparison's terms, and its
    upload decisions (client -> bool) where the run records them."""
    import jax
    init = jax.tree.map(np.asarray, fed.init_params_fn(
        jax.random.split(jax.random.key(seed))[1]))
    side = {"eff": state["prev_grads"],
            "residuals": {int(c): t for c, t in state["ef"].items()},
            "global_delta": jax.tree.map(
                lambda g, g0: np.asarray(g, np.float32) - g0,
                state["global_params"], init)}
    follow = None
    if traffic["algorithm"] == "vafl":
        side["values"] = np.asarray(state["policy"]["known_V"], np.float64)
        if traffic["compressor"] != "identity":
            n = len(side["values"])
            follow = {i: i in side["residuals"] for i in range(n)}
    return side, follow


def run(spec, seed: int, seconds: float, traced: bool, t_start: float,
        out_dir: str) -> dict:
    import jax
    from repro.obs import compile_count, install

    install()
    cfg, traffic, cell = spec["config"], spec["traffic"], spec["cell_file"]
    n = cfg["num_clients"]
    phases = {"start": time.perf_counter() - t_start}
    clock = time.perf_counter()

    def phase(name):
        nonlocal clock
        now = time.perf_counter()
        phases[name] = now - clock
        clock = now

    data = datagen.make(seed, cfg["data"], n)
    phase("data")
    fed, fd = _build(cfg, traffic, data, seed)
    phase("build")
    windows = max(1, int(seconds // cell["window_s"]))
    state, warm = _warm_up(fed, n, windows)
    expected = end_state(warm)
    del warm
    _drain(jax)
    phase("warm_up")
    setup_s = time.perf_counter() - t_start

    obs = None
    trace_dir = os.path.join(out_dir, "trace")
    obs_path = os.path.join(out_dir, "obs.jsonl")
    if traced:
        from repro.obs import ObsConfig
        obs = ObsConfig(trace=True, trace_jsonl=obs_path)
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options(
            jax))
    c0 = compile_count()
    t0 = time.perf_counter()
    res = fed.run(mode="event", rounds=windows, obs=obs)
    _drain(jax)
    window_s = time.perf_counter() - t0
    if traced:
        jax.profiler.stop_trace()
    compiles = compile_count() - c0
    events = windows * n
    if res.comm.broadcasts != events:
        raise RuntimeError(f"{res.comm.broadcasts} downloads for {events} "
                           "events: the run did not commit its budget")
    uploads = res.comm.model_uploads
    timed_mismatches = mismatches(end_state(res), expected)
    stats = jax.devices()[0].memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    del res

    side, follow = _program_side(fed, state, traffic, seed)
    del fed, state
    clock = time.perf_counter()
    upd = reference.updates(cfg, traffic, {**data, **{
        k: getattr(fd, k) for k in ("images", "labels", "mask")}}, seed)
    phase("reference_updates")
    ref = reference.commits(cfg, traffic, upd, seed, follow=follow,
                            tie_margin=cell["tie_margin"])
    phase("reference_commits")
    detail = {}
    found = compare.numbers(side, ref, detail)
    found["timed_run_mismatches"] = timed_mismatches
    correct, rows = compare.judge(found, cell["limits"])
    phase("compare")

    out = {"correct": correct, "attempted": events, "failed": 0,
           "setup_s": setup_s, "memory_peak_bytes": memory_peak,
           "checks": rows,
           "e2e": {"events_per_s": events / window_s},
           "notes": {"windows": windows, "events": events,
                     "uploads": uploads, "window_s": window_s,
                     "compiles_in_window": compiles, "phases_s": phases,
                     "worst": detail}}
    if traced:
        devices = trace.extract(trace_dir)
        reduced = trace.reduce(devices, trace.load_layers(spec["layers"]))
        spans = []
        if os.path.exists(obs_path):
            from repro.obs import read_jsonl
            spans = read_jsonl(obs_path)[1]
        out["ctx"] = SimpleNamespace(
            cfg=cfg, traffic=traffic, driver="closed_loop", events=events,
            uploads=uploads, window_s=window_s, device=reduced, spans=spans,
            peaks=spec["peaks"], flops=flops)
        out["busy_s"] = reduced["busy_s"]
        out["breakdown"] = {"device_ops": reduced["top_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
        out["notes"]["unassigned_device_s"] = reduced["unassigned_s"]
    return out


def _trace_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts
