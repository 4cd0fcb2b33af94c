"""Batched async execution engine (docs/ASYNC_ENGINE.md).

Per-client state lives in device-resident stacked pytrees (leading
axis = client) instead of Python lists; each scheduler window of up to
``max_batch`` completions runs as ONE vmapped jitted local update over
the gathered sub-stack, and accepted uploads flow through a
FedBuff-style buffer flushed as a staleness-weighted mean every
``buffer_size`` arrivals.

Three performance layers on top of that execution model:

* **Full-window fast path.**  At ``max_batch=0`` (the throughput
  default) a window is a *permutation* of all N clients, so the engine
  skips the three O(N·|params|) stack copies entirely: the update runs
  over the stacked state in CLIENT order with per-client RNG keys
  permuted to their arrival positions (bit-exact with the gathered
  path — ``make_local_update_keyed``), prev_grads becomes the update's
  eff output by reference, and the download write-back is a pure gather
  of version trees (no scatter).

* **Sharded client state.**  ``FLRunConfig.shard_clients`` places the
  stacked pytrees on a 1-D ``("clients",)`` mesh
  (``repro.distributed.sharding.client_state_sharding``): the vmapped
  window update is data-parallel across devices, and the engine's jit
  set (``_engine_jits``) keeps stacked outputs constrained to the
  client axis.  A 1-device mesh is bit-exact with the unsharded engine.

* **One-window-deep pipeline.**  Host work that cannot affect gating —
  rescheduling the window's clients, popping the NEXT window, gathering
  its data — happens between dispatching a window's device work and
  blocking on its gating inputs (whose device→host copies are started
  asynchronously), so the host never sits idle in front of
  ``np.asarray``.  Eval records hold device scalars until the end of the
  run, the download write-back + prev-grad scatter land as one donated
  jitted commit, and a flush triggered by the window's final event is
  folded into that same call.

The algorithm is the ``UploadPolicy`` / ``Aggregator`` protocol: the
policy's declared stacked inputs (Eq. 1 values, gradient norms) are
computed once per window as a single vmapped dispatch — the one-dispatch
hot path — and its scalar ``decide`` is applied per event in arrival
order; the server-delta threshold is evaluated once per window (at the
mix point).  The compression plumbing is unchanged — codec payloads and
error feedback stay per-client.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

import repro.checkpoint.store as ck

from repro.algorithms.base import Aggregator
from repro.common.pytree import (stacked_index, tree_bytes, tree_gather,
                                 tree_shard)
from repro.compress import DeviceWire, wire_nbytes
from repro.core.aggregation import buffered_coefs, buffered_mix
from repro.core.client import make_local_update, make_local_update_keyed
from repro.core.metrics import CommStats, RoundRecord, RunResult
from repro.core.runtimes.common import (_BROADCAST, _UPLOAD,
                                        _attach_sim_result,
                                        _compressed_broadcast,
                                        _compressed_upload, _enc_seed,
                                        _engine_jits, _event_helpers,
                                        _finish_obs, _make_codecs,
                                        _obs_for_run, _annotate, _span,
                                        _tree_delta, _value_fn)
from repro.core.scheduler import EventScheduler
from repro.obs.console import progress


def _host_async(x):
    """Start a non-blocking device→host copy so the later np.asarray
    completes immediately (no-op for values that are already host-side)."""
    try:
        x.copy_to_host_async()
    except AttributeError:
        pass
    return x


class _AccCache:
    """Per-client Eq. 1 accuracy cache (``FLRunConfig.eval_cache``):
    each client's accuracy term is refreshed at most once every ``every``
    of its own events and the cached scalar reused in between.  Fresh
    rows are gathered and evaluated in power-of-two buckets so the
    number of compiled eval variants stays O(log N)."""

    def __init__(self, num_clients: int, every: int, batch_eval, gather,
                 obs=None):
        self.every = every
        self.batch_eval = batch_eval
        self.gather = gather
        self.obs = obs
        self.acc = np.zeros(num_clients, np.float32)
        # "never evaluated" sorts as infinitely stale
        self.age = np.full(num_clients, np.iinfo(np.int32).max, np.int64)

    def window_accs(self, newp, clients: np.ndarray) -> jnp.ndarray:
        """Accuracies for the window's clients, indexed by ``newp`` rows
        (``clients[r]`` = client id of row r)."""
        need = np.flatnonzero(self.age[clients] >= self.every)
        if self.obs is not None:
            self.obs.eval_cache(hits=len(clients) - len(need),
                                misses=len(need))
        if len(need):
            bucket = 1 << (len(need) - 1).bit_length()
            rows = np.concatenate([need, np.zeros(bucket - len(need),
                                                  np.int64)])
            fresh = np.asarray(self.batch_eval(
                self.gather(newp, jnp.asarray(rows))), np.float32)
            self.acc[clients[need]] = fresh[:len(need)]
            self.age[clients[need]] = 0
        self.age[clients] += 1
        return jnp.asarray(self.acc[clients])


def _entry(ref, row):
    """The tree of one buffer entry: row ``row`` of the stacked ``ref``,
    or ``ref`` itself where ``row`` is None (a codec reconstruction)."""
    return ref if row is None else stacked_index(ref, row)


def _run_event_batched(run_cfg, policy, aggregator, init_params_fn, loss_fn,
                       fed_data, evaluate_fn, client_eval_fn, speed,
                       net=None, avail=None, verbose=False) -> RunResult:
    # the observer comes first: its host spans (run.start, each window and
    # its phases, run.finish) cover the whole run, so that a profiler
    # trace can put every stretch of device idle time down to one of them
    obs = _obs_for_run(run_cfg)
    if obs is not None:                # opt-in device profiler (whole run)
        obs.profile_start()
    N = run_cfg.num_clients
    W = run_cfg.max_batch if run_cfg.max_batch > 0 else N
    W = max(1, min(W, N))
    K = max(1, run_cfg.buffer_size)
    total_events = run_cfg.rounds * N
    # the FedBuff buffer: (stacked_tree, row) references — rows of the
    # window's vmapped output for identity uploads (client ids on the
    # fast path, window positions otherwise), (tree, None) for codec
    # reconstructions; gathered/stacked only at flush time
    buffer: list = []
    buf_stale: list = []              # their staleness weights s(tau)
    records: list = []
    last_eval = (None, None)           # (server_version, acc device scalar)
    ev = 0
    pre_d = None                       # next window's pre-dispatched data
    nxt = None

    with _span(obs, "run.start"):
        rng = jax.random.key(run_cfg.seed)
        rng, krng = jax.random.split(rng)
        global_params = init_params_fn(krng)
        comm = CommStats(model_bytes=tree_bytes(global_params))
        codec, bcodec, ef = _make_codecs(run_cfg)
        sq_diff = _value_fn(run_cfg)

        local_update = make_local_update(loss_fn, run_cfg.local)
        keyed_update = make_local_update_keyed(loss_fn, run_cfg.local)
        data = {"images": jnp.asarray(fed_data.images),
                "labels": jnp.asarray(fed_data.labels),
                "mask": jnp.asarray(fed_data.mask)}

        sharding = None
        encode_on = None
        if run_cfg.shard_clients:
            from repro.distributed.sharding import client_state_sharding
            sharding = client_state_sharding(N)
            # the codec's Pallas kernel cannot be partitioned over the
            # mesh: each upload is encoded on one device
            encode_on = sharding.mesh.devices.flat[0]
        ops = _engine_jits(sharding)

        # device-resident stacked per-client state: no Python lists of
        # full pytrees, everything gathers/scatters on a leading axis
        # (sharded on the ("clients",) mesh when configured)
        client_params = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (N,) + x.shape), global_params)
        prev_grads = jax.tree.map(
            lambda x: jnp.zeros((N,) + x.shape, jnp.float32), global_params)
        if sharding is not None:
            client_params = tree_shard(client_params, sharding)
            prev_grads = tree_shard(prev_grads, sharding)
            data = tree_shard(data, sharding)
        model_version = np.zeros(N, int)  # version each client downloaded
        server_version = 0
        prev_global = global_params
        prev_prev_global = global_params

        batch_eval, values_fn, norms_fn = _event_helpers(
            run_cfg, client_eval_fn, sq_diff)
        acc_cache = (_AccCache(N, run_cfg.eval_cache, batch_eval,
                               ops.gather, obs=obs)
                     if policy.needs_values and run_cfg.eval_cache > 0
                     else None)
        # a window's final flush folds into the commit only when the
        # default flush math applies (a plugin aggregator's override must
        # stay in charge of its own mixing)
        foldable_flush = type(aggregator).flush_mix is Aggregator.flush_mix

        sched = EventScheduler(N, speed, network=net, availability=avail,
                               obs=obs)
        # a reactive scenario consumes per-event payload bytes (or
        # availability draws) at reschedule time, so the pipeline's
        # reschedule+pop-ahead must wait for the window's upload decisions
        reactive = sched.reactive

        # full-run checkpoint-resume (docs/RESILIENCE.md).  The pipeline
        # is one window deep, so a checkpoint taken at the end of a loop
        # body must bundle the already-popped NEXT window alongside the
        # scheduler snapshot; buffered updates are materialized to host
        # trees (their stacked-window sources don't outlive the iteration)
        # and restored as unstacked trees — exactly how codec
        # reconstructions enter the buffer, so the flush math is
        # unchanged.
        ckpt_path, ckpt_every = (run_cfg.checkpoint_path,
                                 run_cfg.checkpoint_every)
        fingerprint = (ck.run_fingerprint(run_cfg, "batched", global_params)
                       if ckpt_path else None)

        if run_cfg.resume and ckpt_path and os.path.exists(ckpt_path):
            st = ck.load_run_state(ckpt_path, fingerprint)
            ev = int(st["event"])
            rng = jax.random.wrap_key_data(jnp.asarray(st["rng"]))
            global_params = ck.tree_to_device(st["global_params"])
            prev_global = ck.tree_to_device(st["prev_global"])
            prev_prev_global = ck.tree_to_device(st["prev_prev_global"])
            client_params = ck.tree_to_device(st["client_params"])
            prev_grads = ck.tree_to_device(st["prev_grads"])
            if sharding is not None:
                client_params = tree_shard(client_params, sharding)
                prev_grads = tree_shard(prev_grads, sharding)
            model_version = np.asarray(st["model_version"], int).copy()
            server_version = int(st["server_version"])
            comm.__dict__.update(st["comm"])
            records = list(st["records"])
            if st["last_eval"] is not None:
                last_eval = (int(st["last_eval"][0]), st["last_eval"][1])
            buffer = [(ck.tree_to_device(t), None) for t in st["buffer"]]
            buf_stale = list(st["buf_stale"])
            if st["policy"] is not None:
                policy.set_state(st["policy"])
            ef.residuals = {int(c): ck.tree_to_device(t)
                            for c, t in st["ef"].items()}
            if acc_cache is not None and st["acc_cache"] is not None:
                acc_cache.acc = np.asarray(st["acc_cache"]["acc"],
                                           np.float32).copy()
                acc_cache.age = np.asarray(st["acc_cache"]["age"],
                                           np.int64).copy()
            sched.restore(st["sched"])
            if st["nxt"] is not None:
                times = np.asarray(st["nxt"][0], np.float64)
                idx_np = np.asarray(st["nxt"][1], np.int64)
            elif ev < total_events:
                # the writer's event budget ended at this checkpoint, so
                # it never popped a next window; a resume that EXTENDS the
                # run (rounds is outside the fingerprint) pops it now —
                # the restored scheduler is exactly the state the longer
                # run popped from mid-body
                times, idx_np = sched.pop_window(min(W, total_events - ev))
            else:
                times, idx_np = np.empty(0), np.empty(0, int)
            if obs is not None:
                if st.get("obs_metrics"):
                    obs.metrics.restore(st["obs_metrics"])
                obs.checkpoint(ev, obs.host_now(), restored=True)
        else:
            times, idx_np = (sched.pop_window(min(W, total_events))
                             if total_events
                             else (np.empty(0), np.empty(0, int)))

    def flush(sim=None):
        nonlocal global_params, prev_global, prev_prev_global, server_version
        if obs is not None:
            obs.flush(len(buffer), sim)
        prev_prev_global = prev_global
        prev_global = global_params
        if len(buffer) == 1:          # bit-exact sequential mix (K=1 path)
            global_params = buffered_mix(
                global_params, [_entry(*buffer[0])], buf_stale,
                aggregator.mix_rate, mix=aggregator.mix)
        else:
            groups: list = []         # consecutive same-source rows
            for ref, row in buffer:
                if row is not None and groups and groups[-1][0] is ref:
                    groups[-1][1].append(row)
                else:
                    groups.append((ref, [row]))
            if len(groups) == 1:      # common case: one source, jitted gather
                src, rows = groups[0]
            else:                     # buffer spans windows/codec payloads
                src = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, 0),
                    *[jax.tree.map(lambda x: x[None], ref) if rows == [None]
                      else tree_gather(ref, np.asarray(rows))
                      for ref, rows in groups])
                rows = range(len(buffer))
            coef, rho_sbar = buffered_coefs(buf_stale, aggregator.mix_rate)
            global_params = aggregator.flush_mix(
                global_params, src, np.asarray(rows, np.int32), coef,
                rho_sbar)
        server_version += 1
        buffer.clear()
        buf_stale.clear()

    def _save_ckpt():
        h0 = obs.host_now() if obs is not None else 0.0
        state = {
            "event": ev,
            "rng": np.asarray(jax.random.key_data(rng)),
            "global_params": ck.tree_to_host(global_params),
            "prev_global": ck.tree_to_host(prev_global),
            "prev_prev_global": ck.tree_to_host(prev_prev_global),
            "client_params": ck.tree_to_host(client_params),
            "prev_grads": ck.tree_to_host(prev_grads),
            "model_version": model_version.copy(),
            "server_version": server_version,
            "comm": dict(comm.__dict__),
            # deferred eval scalars resolve into COPIES — the live
            # records keep overlapping the next window's compute
            "records": [dataclasses.replace(r, global_acc=float(r.global_acc))
                        for r in records],
            "last_eval": (None if last_eval[0] is None
                          else (int(last_eval[0]), float(last_eval[1]))),
            "buffer": [ck.tree_to_host(_entry(ref, row))
                       for ref, row in buffer],
            "buf_stale": list(buf_stale),
            "policy": policy.state(),
            "ef": {c: ck.tree_to_host(t) for c, t in ef.residuals.items()},
            "acc_cache": (None if acc_cache is None else
                          {"acc": acc_cache.acc.copy(),
                           "age": acc_cache.age.copy()}),
            "nxt": (None if nxt is None else
                    (np.asarray(nxt[0], np.float64),
                     np.asarray(nxt[1], np.int64))),
            "sched": sched.snapshot(),
            "obs_metrics": obs.metrics.snapshot() if obs is not None else None,
        }
        ck.save_run_state(ckpt_path, state, fingerprint)
        if obs is not None:
            obs.checkpoint(ev, h0)

    if obs is not None:
        obs.sampler_start()            # opt-in live metric sampler
    while len(idx_np):
        t_now = float(times[-1])
        w = len(idx_np)
        full = w == N                  # a full window = client permutation
        # the window's record (written at the commit) and its host phases
        with _annotate(obs, "window", size=w):
            h0 = obs.host_now() if obs is not None else 0.0

            # ---- dispatch the window's device work --------------------
            with _span(obs, "window.dispatch"):
                rng, urng = jax.random.split(rng)
                if full:
                    # run in client order with keys permuted to arrival
                    # positions: bit-exact with the gathered path, but the
                    # three O(N*|params|) stack copies (gather, prev-grad
                    # scatter, download scatter) vanish.  row(client i)
                    # == i.
                    inv = np.empty(N, np.int64)
                    inv[idx_np] = np.arange(N)
                    keys = jax.random.split(urng, N)[jnp.asarray(inv)]
                    sub_base = client_params
                    newp, eff, _ = keyed_update(client_params, data, keys)
                    row_of = idx_np            # event j -> row in newp/eff
                else:
                    idx = jnp.asarray(idx_np)
                    sub_base = ops.gather(client_params, idx)
                    d_w = pre_d if pre_d is not None else ops.gather(data,
                                                                     idx)
                    newp, eff, _ = local_update(sub_base, d_w, urng)
                    row_of = np.arange(w)
                pre_d = None

                # the policy's declared stacked inputs: ONE vmapped
                # dispatch per window each, with the device->host copy
                # started immediately so the host can keep dispatching
                # while it lands
                V_dev = norms_dev = None
                if policy.needs_values:
                    if acc_cache is not None:
                        # rows of newp map to clients: identity on the
                        # fast path (client order), the window's arrival
                        # ids otherwise
                        accs = acc_cache.window_accs(
                            newp, np.arange(N) if full else idx_np)
                    else:
                        accs = batch_eval(newp)
                    pg_w = prev_grads if full else ops.gather(
                        prev_grads, jnp.asarray(idx_np))
                    V_dev = _host_async(values_fn(pg_w, eff, accs))
                if policy.needs_norms:
                    norms_dev = _host_async(norms_fn(eff))

            # ---- the one-window-deep pipeline --------------------------
            # everything gating CANNOT change happens before we block on
            # the gating inputs: restart each client from its own
            # completion time (window execution must not barrier the
            # simulated clock), pop the NEXT window, and pre-dispatch its
            # data gather.  A reactive scenario defers all of this to
            # after the decision loop — the network model needs each
            # event's actual payload bytes.
            with _span(obs, "window.pipeline"):
                nxt = None
                if not reactive:
                    for j in range(w):
                        sched.schedule(int(idx_np[j]), start=float(times[j]))
                    remaining = total_events - ev - w
                    nxt = (sched.pop_window(min(W, remaining)) if remaining
                           else None)
                    if nxt is not None and len(nxt[1]) < N:
                        pre_d = ops.gather(data, jnp.asarray(nxt[1]))

            # the host blocks here, on the gating inputs' device->host copy
            with _span(obs, "window.wait"):
                V_w = (None if V_dev is None
                       else np.asarray(V_dev, np.float64)[
                           row_of if full else slice(None)])
                norms_w = (None if norms_dev is None
                           else np.asarray(norms_dev, np.float64)[
                               row_of if full else slice(None)])

            with _span(obs, "window.decide"):
                # the policy's server-side threshold (EAFLM Eq. 3) is
                # evaluated once per WINDOW, from the deltas as of window
                # start — an intentional engine approximation: mid-window
                # flushes (whenever buffer_size < window) advance the
                # server deltas without re-thresholding.  The sequential
                # engine recomputes per event; max_batch=1/buffer_size=1
                # is the bit-exact configuration.
                thr = policy.window_threshold(
                    lambda: _tree_delta(prev_global, prev_prev_global))

                dl_rel = np.empty(w, np.int64)  # per-event ver_trees index
                ver_trees: list = []            # distinct globals downloaded
                ver_pos: dict = {}              # server_version -> position
                enc_downloads: list = []        # per-client lossy downlinks
                sent: list = []                 # codec uploads, by event
                pending = None                  # final flush folded in commit
                ev_up = np.zeros(w, np.int64)   # per-event on-the-wire bytes
                ev_down = np.zeros(w, np.int64)
                for j in range(w):
                    i = int(idx_np[j])
                    r = int(row_of[j])
                    t_j = float(times[j])
                    u0, d0 = comm.uplink_bytes, comm.downlink_bytes
                    if policy.reports:
                        comm.record_report(1)
                        if obs is not None:
                            obs.report(i, t_j)
                    upload = policy.decide(
                        i, None if V_w is None else float(V_w[j]),
                        None if norms_w is None else float(norms_w[j]), thr)

                    if upload:
                        with _span(obs, "upload_path", client=i):
                            staleness = server_version - model_version[i]
                            if codec.is_identity:
                                buffer.append((newp, r))
                                comm.record_upload(1)
                                if obs is not None:
                                    obs.upload(i, t_j,
                                               staleness=int(staleness),
                                               nbytes=comm.model_bytes,
                                               codec=codec.name)
                            else:
                                # the reconstruction, an unstacked tree;
                                # its bytes are counted after the loop
                                recon, wire = _compressed_upload(
                                    codec, ef, sub_base, r, newp, r, i,
                                    _enc_seed(run_cfg, ev + j, i, _UPLOAD),
                                    obs=obs, device=encode_on)
                                buffer.append((recon, None))
                                sent.append((j, i, t_j, staleness, wire))
                            buf_stale.append(
                                aggregator.stale_weight(staleness))
                            if len(buffer) >= K:
                                if (j == w - 1 and len(buffer) > 1
                                        and foldable_flush and bcodec is None
                                        and all(ref is newp
                                                for ref, _ in buffer)):
                                    # window's final flush: fold into the
                                    # commit call (only this event can
                                    # download the new version)
                                    rows = np.asarray(
                                        [rr for _, rr in buffer], np.int32)
                                    coef, rho_sbar = buffered_coefs(
                                        buf_stale, aggregator.mix_rate)
                                    pending = (rows, coef, rho_sbar)
                                    if obs is not None:
                                        obs.flush(len(buffer), t_j,
                                                  folded=True)
                                    server_version += 1
                                    buffer.clear()
                                    buf_stale.clear()
                                else:
                                    flush(t_j)

                    if bcodec is None:
                        comm.record_broadcast(1)
                        if pending is not None and server_version not in \
                                ver_pos:
                            dl_rel[j] = -1  # the in-commit flushed global
                        else:
                            if server_version not in ver_pos:
                                ver_pos[server_version] = len(ver_trees)
                                ver_trees.append(global_params)
                            dl_rel[j] = ver_pos[server_version]
                    else:
                        enc_downloads.append(_compressed_broadcast(
                            bcodec, comm, global_params, 1,
                            _enc_seed(run_cfg, ev + j, i, _BROADCAST),
                            obs=obs))
                    model_version[i] = server_version
                    ev_up[j] = comm.uplink_bytes - u0
                    ev_down[j] = comm.downlink_bytes - d0
                    if obs is not None:
                        obs.broadcast(i, t_j, nbytes=int(ev_down[j]),
                                      codec=(None if bcodec is None
                                             else bcodec.name))

                # the codec uploads' wire bytes: the device round trips'
                # counts read back in one copy, now that the decisions are
                # made
                for (j, i, t_j, staleness, wire), nbytes in zip(
                        sent, wire_nbytes([u[-1] for u in sent])):
                    comm.record_upload(1, nbytes=nbytes)
                    ev_up[j] += nbytes
                    if obs is not None:
                        obs.upload(i, t_j, staleness=int(staleness),
                                   nbytes=nbytes, codec=codec.name,
                                   device_codec=isinstance(wire, DeviceWire))

                if reactive:
                    # byte-aware reschedule: each client restarts from its
                    # own completion time plus the link delay its actual
                    # payload cost
                    for j in range(w):
                        sched.schedule(int(idx_np[j]), start=float(times[j]),
                                       upload_bytes=int(ev_up[j]),
                                       download_bytes=int(ev_down[j]))
                    remaining = total_events - ev - w
                    nxt = (sched.pop_window(min(W, remaining)) if remaining
                           else None)
                    if nxt is not None and len(nxt[1]) < N:
                        pre_d = ops.gather(data, jnp.asarray(nxt[1]))
                else:
                    # already rescheduled (pipeline); ledger the bytes only
                    for j in range(w):
                        sched.account_bytes(int(idx_np[j]), int(ev_up[j]),
                                            int(ev_down[j]))

            # ---- commit: flush remainder + download write-back +
            # prev-grad scatter, ONE donated jitted call -----------------
            with _span(obs, "window.commit"):
                if any(ref is newp for ref, _ in buffer):
                    # detach leftover buffer entries from the window output
                    # before it goes out of scope: under gating a
                    # partially-full buffer would otherwise pin one full
                    # (w, ...) stack per window until the flush — gather
                    # just the buffered rows instead
                    rows = np.asarray([r for ref, r in buffer
                                       if ref is newp])
                    sub = tree_gather(newp, rows)
                    fresh = iter(range(len(rows)))
                    buffer[:] = [(sub, next(fresh)) if ref is newp
                                 else (ref, r) for ref, r in buffer]
                sub_base = None  # release the window's download base

                if pending is not None:
                    prev_prev_global = prev_global
                    prev_global = global_params
                if bcodec is None:
                    # the version count varies per window under gating, so
                    # the stack is padded to the next power of two —
                    # O(log W) compiled variants instead of one per
                    # distinct count (padding rows are never indexed)
                    if len(ver_trees) > 1:
                        bucket = 1 << (len(ver_trees) - 1).bit_length()
                        padded = ver_trees + [ver_trees[-1]] * (
                            bucket - len(ver_trees))
                    else:
                        padded = ver_trees
                    vstack = ops.stack(tuple(padded))
                    # fast path: re-index the per-event versions by CLIENT
                    # (row i of the new stack belongs to client i, whose
                    # event was j = inv[i]); sub-full windows keep arrival
                    # order
                    rel_np = dl_rel[inv] if full else dl_rel
                    rel = jnp.asarray(np.where(rel_np < 0, len(padded),
                                               rel_np))
                    if full:
                        if pending is not None:
                            global_params, client_params, prev_grads = \
                                ops.commit_full_flush(global_params, vstack,
                                                      rel, eff, newp,
                                                      *pending)
                        else:
                            client_params, prev_grads = ops.commit_full(
                                vstack, rel, eff)
                    else:
                        idx = jnp.asarray(idx_np)
                        if pending is not None:
                            global_params, client_params, prev_grads = \
                                ops.commit_win_flush(
                                    global_params, client_params,
                                    prev_grads, idx, vstack, rel, eff, newp,
                                    *pending)
                        else:
                            client_params, prev_grads = ops.commit_win(
                                client_params, prev_grads, idx, vstack,
                                rel, eff)
                else:
                    assert pending is None  # bcodec downloads never fold
                    if full:
                        # client order: client i received
                        # enc_downloads[inv[i]]
                        client_params = ops.place(ops.stack(
                            tuple(enc_downloads[int(v)] for v in inv)))
                        prev_grads = eff
                    else:
                        idx = jnp.asarray(idx_np)
                        client_params = ops.scatter_donated(
                            client_params, idx,
                            ops.stack(tuple(enc_downloads)))
                        prev_grads = ops.scatter_donated(prev_grads, idx,
                                                         eff)

            if obs is not None:
                # one span per window: sim bounds = first/last completion,
                # host duration = dispatch through commit (this point)
                obs.window(w, float(times[0]), t_now, h0)
        prev_ev, ev = ev, ev + w
        epe = run_cfg.events_per_eval
        crossed = ev // epe - prev_ev // epe
        if crossed:
            # eval records hold device scalars until the end of the run so
            # evaluation overlaps the next window's compute; a record whose
            # global model is bit-identical to the previous one (no flush
            # since) reuses its scalar outright
            with _annotate(obs, "eval"):
                h0e = obs.host_now() if obs is not None else 0.0
                reused = last_eval[0] == server_version
                if reused:
                    acc = last_eval[1]  # bit-identical model: reuse (exact)
                else:
                    acc = _host_async(evaluate_fn(global_params))
                    last_eval = (server_version, acc)
                if obs is not None:
                    # the acc scalar stays deferred — the hook never reads
                    obs.eval_event(ev, t_now, h0e, boundaries=crossed,
                                   reused=reused)
                records.append(RoundRecord(
                    round=ev, time=t_now, global_acc=acc,
                    uploads_so_far=comm.model_uploads,
                    boundaries_crossed=crossed))
            if verbose:
                progress(f"[{run_cfg.algorithm}/batched] ev {ev:5d} "
                         f"t={t_now:8.1f} acc={float(acc):.4f} "
                         f"uploads={comm.model_uploads}")
        if ckpt_every and ev // ckpt_every > prev_ev // ckpt_every:
            with _annotate(obs, "checkpoint"):
                _save_ckpt()

        if nxt is None:
            break
        times, idx_np = nxt

    if obs is not None:
        obs.sampler_stop()
    with _span(obs, "run.finish"):
        if buffer:  # partial buffer at run end — flush so no update is lost
            flush(float(sched.now))
        for r in records:              # resolve the deferred eval scalars
            r.global_acc = float(r.global_acc)
        res = _attach_sim_result(RunResult(
            run_cfg.algorithm, records, comm,
            run_cfg.target_acc).finalize_target(), sched)
    if obs is not None:
        obs.profile_stop()
    return _finish_obs(res, obs)
