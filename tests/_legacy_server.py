"""FROZEN pre-refactor FL server runtimes (PR 3 golden reference).

Verbatim copy of ``repro/core/server.py`` as of commit 0c5660d, BEFORE the
algorithm string-branches were extracted into the ``repro.algorithms``
protocol.  tests/test_algorithms.py runs this legacy implementation next
to the refactored runtimes on golden seeds and asserts bit-identical
``RunResult`` records — do NOT edit this file except to keep its imports
resolving.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.pytree import (stacked_index, tree_bytes, tree_gather,
                                 tree_scatter, tree_stack, tree_sq_norm)
from repro.compress import ErrorFeedback, get_codec
# the wire-plane codec path: the runtimes' device round trips match it
from repro.compress import compress_payload as compress_update
from repro.core import value as value_lib
from repro.core.aggregation import (aggregate_or_keep, async_mix,
                                    buffered_coefs, buffered_mean,
                                    buffered_mix, staleness_weight)
from repro.core.client import LocalSpec, make_local_update
from repro.core.metrics import CommStats, RoundRecord, RunResult
from repro.core.scheduler import EventScheduler, SpeedModel

ALGORITHMS = ("afl", "vafl", "eaflm", "fedavg")


@dataclass
class FLRunConfig:
    algorithm: str = "vafl"
    num_clients: int = 7
    rounds: int = 200                  # R (server rounds / event budget)
    local: LocalSpec = field(default_factory=LocalSpec)
    target_acc: float = 0.94
    eval_every: int = 1
    seed: int = 0
    # EAFLM constants (paper: xi_d = 1/D, D = 1, alpha = 0.98).  beta and m
    # are unspecified "constant coefficients"; the alpha^2*beta*m^2 product
    # is treated as ONE calibrated constant (m folded into beta, m=1),
    # because m=N's quadratic growth silences the rule entirely for larger
    # federations on our testbed.  beta=1e-2 reproduces the paper's 36-58%
    # suppression range across experiments a-d (benchmarks/table3_ccr.py).
    eaflm_alpha: float = 0.98
    eaflm_beta: float = 1e-2
    # update compression (repro.compress): codec spec for accepted uploads
    # ("identity", "int8", "int4", "topk0.1", "topk0.1_int8", ...) and an
    # optional codec for the model broadcast (no error feedback there —
    # clients train from the lossy model they actually received).
    compressor: str = "identity"
    broadcast_compressor: Optional[str] = None
    error_feedback: bool = True        # SGD-EF residuals on the upload path
    # partial participation: fraction of clients in the round's set S
    # (Algorithm 1 "for each i in S"); 1.0 = all clients every round
    participation: float = 1.0
    # event-driven runtime
    mix_rate: float = 0.5              # rho
    staleness_kind: str = "poly"       # 'poly' | 'const'
    events_per_eval: int = 7
    value_backend: Callable = None     # optional kernel for ||dg||^2
    # batched async engine (docs/ASYNC_ENGINE.md): engine="batched" keeps
    # per-client state device-resident as stacked pytrees and executes each
    # scheduler window (up to max_batch completions, pop_window) as ONE
    # vmapped local update; accepted uploads flow through a FedBuff-style
    # buffer of buffer_size reconstructions mixed as a staleness-weighted
    # mean.  max_batch=0 means "window = num_clients".  The max_batch=1 +
    # buffer_size=1 configuration reproduces the sequential per-event loop
    # exactly (tests/test_async_engine.py).
    engine: str = "sequential"         # 'sequential' | 'batched'
    max_batch: int = 0                 # pop_window bound (0 = num_clients)
    buffer_size: int = 1               # K reconstructions buffered per mix


def _value_fn(cfg: FLRunConfig):
    if cfg.value_backend is not None:
        return cfg.value_backend
    from repro.common.pytree import tree_sq_diff_norm
    return tree_sq_diff_norm


# ------------------------------------------------- compression plumbing ---

def _make_codecs(run_cfg: FLRunConfig):
    codec = get_codec(run_cfg.compressor)
    bcodec = None
    if run_cfg.broadcast_compressor not in (None, "", "identity", "none"):
        bcodec = get_codec(run_cfg.broadcast_compressor)
    return codec, bcodec, ErrorFeedback(enabled=run_cfg.error_feedback)


_UPLOAD, _BROADCAST = 1, 2


def _participation_mask(part_rng, participation: float, n: int) -> np.ndarray:
    """The round's participating set S — ONE sampler shared by the
    round-based runtime and the sync barrier so the FedAvg baseline stays
    comparable under partial participation."""
    if participation < 1.0:
        k = max(1, int(round(participation * n)))
        part = np.zeros(n, bool)
        part[part_rng.choice(n, size=k, replace=False)] = True
        return part
    return np.ones(n, bool)


def _enc_seed(run_cfg: FLRunConfig, step: int, i: int, kind: int) -> int:
    """Deterministic per-transfer seed: payloads are reproducible from the
    run seed alone, and stochastic rounding decorrelates across transfers.
    Multiplicative mixing over (seed, kind, step, client) so distinct
    transfers never share a seed (additive offsets would collide, e.g.
    round-t broadcast vs a later client upload)."""
    h = (run_cfg.seed ^ (kind * 0x9E3779B9)) & 0xFFFFFFFF
    h = (h * 1_000_003 + step) & 0xFFFFFFFF
    h = (h * 1_000_003 + i) & 0xFFFFFFFF
    return h


def _tree_delta(a, b):
    return jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)


def _tree_apply_delta(base, delta):
    return jax.tree.map(
        lambda b, d: (b.astype(jnp.float32) + d.astype(jnp.float32)
                      ).astype(b.dtype), base, delta)


def _compressed_upload(codec, ef, comm, base, client_tree, i, seed):
    """One client's compressed upload: encode codec(delta vs ``base``, the
    model the client downloaded) with error feedback, account the wire
    bytes, and return the reconstruction the server actually receives."""
    delta = _tree_delta(client_tree, base)
    payload, decoded = compress_update(codec, ef, i, delta, seed=seed)
    comm.record_upload(1, nbytes=payload.nbytes)
    return _tree_apply_delta(base, decoded)


def _compressed_broadcast(bcodec, comm, params, n, seed):
    """Encode one model broadcast to ``n`` clients; returns the lossy
    model they actually receive (no EF on the downlink — clients train
    from what arrived)."""
    bp = bcodec.encode(params, seed=seed)
    comm.record_broadcast(n, nbytes=n * bp.nbytes)
    return bcodec.decode(bp)


# =========================================================== round-based ===

def run_round_based(run_cfg: FLRunConfig, *, init_params_fn, loss_fn,
                    fed_data, evaluate_fn, client_eval_fn=None,
                    verbose: bool = False) -> RunResult:
    """Faithful Algorithm 1.  init_params_fn(rng) -> params;
    loss_fn(params, batch) -> (loss, aux); fed_data: FederatedData;
    evaluate_fn(params) -> global test Acc;
    client_eval_fn(params) -> Acc (defaults to evaluate_fn)."""
    alg = run_cfg.algorithm
    assert alg in ALGORITHMS
    N = run_cfg.num_clients
    client_eval_fn = client_eval_fn or evaluate_fn
    rng = jax.random.key(run_cfg.seed)
    rng, krng = jax.random.split(rng)
    global_params = init_params_fn(krng)
    stacked = jax.tree.map(lambda x: jnp.broadcast_to(x, (N,) + x.shape), global_params)
    prev_grads = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), stacked)
    prev_global = global_params  # for EAFLM server-delta threshold
    prev_prev_global = global_params

    local_update = make_local_update(loss_fn, run_cfg.local)
    sq_diff = _value_fn(run_cfg)
    counts = jnp.asarray(fed_data.counts, jnp.float32)
    data = {"images": jnp.asarray(fed_data.images),
            "labels": jnp.asarray(fed_data.labels),
            "mask": jnp.asarray(fed_data.mask)}

    comm = CommStats(model_bytes=tree_bytes(global_params))
    codec, bcodec, ef = _make_codecs(run_cfg)
    client_base = global_params   # what clients actually received last
    records = []
    batch_eval = jax.jit(jax.vmap(client_eval_fn))

    values_fn = jax.jit(lambda gp, gc, accs: value_lib.communication_values_stacked(
        gp, gc, accs, N, sq_diff_fn=sq_diff))
    grad_norms_fn = jax.jit(jax.vmap(tree_sq_norm))

    part_rng = np.random.RandomState(run_cfg.seed + 101)

    for t in range(1, run_cfg.rounds + 1):
        rng, urng = jax.random.split(rng)
        stacked, eff_grads, losses = local_update(stacked, data, urng)
        client_accs = batch_eval(stacked)

        # the round's participating set S (Algorithm 1 "for each i in S")
        part = _participation_mask(part_rng, run_cfg.participation, N)

        if alg == "vafl":
            vals = values_fn(prev_grads, eff_grads, client_accs)
            comm.record_report(int(part.sum()))
            v_np = np.asarray(vals, np.float64)
            v_part = v_np[part]
            mask = part & (v_np >= v_part.mean())
            if not mask.any():
                mask = part & (v_np >= v_part.max())
            vals_list = [float(v) for v in v_np]
        elif alg == "eaflm":
            delta = _tree_delta(prev_global, prev_prev_global)
            thr = value_lib.eaflm_threshold([delta], run_cfg.eaflm_alpha,
                                            run_cfg.eaflm_beta, 1)
            norms = grad_norms_fn(eff_grads)
            comm.record_report(int(part.sum()))
            mask = part & np.asarray(norms > thr)
            vals_list = [float(v) for v in np.asarray(norms)]
        else:  # afl / fedavg: every participant uploads every round
            mask = part.copy()
            vals_list = None
        if not mask.any():  # guard (eaflm may suppress all participants)
            norms_np = np.asarray(grad_norms_fn(eff_grads), np.float64)
            norms_np[~part] = -np.inf
            mask = norms_np == norms_np.max()
        if codec.is_identity:
            comm.record_upload(int(mask.sum()))
        else:
            # each selected client ships codec(delta vs its download base)
            # with error feedback; the server aggregates reconstructions
            sel = [int(i) for i in np.flatnonzero(mask)]
            recon = [_compressed_upload(codec, ef, comm, client_base,
                                        stacked_index(stacked, i), i,
                                        _enc_seed(run_cfg, t, i, _UPLOAD))
                     for i in sel]
            if sel:   # one scatter per leaf, not one stack copy per client
                stacked = tree_scatter(stacked, jnp.asarray(sel),
                                       tree_stack(recon))

        prev_prev_global = prev_global
        prev_global = global_params
        global_params = aggregate_or_keep(global_params, stacked,
                                          jnp.asarray(mask), counts)
        # broadcast the new global model to every client
        if bcodec is None:
            comm.record_broadcast(N)
            client_base = global_params
        else:
            client_base = _compressed_broadcast(
                bcodec, comm, global_params, N,
                _enc_seed(run_cfg, t, 0, _BROADCAST))
        stacked = jax.tree.map(lambda x: jnp.broadcast_to(x, (N,) + x.shape),
                               client_base)
        prev_grads = eff_grads

        if t % run_cfg.eval_every == 0:
            acc = float(evaluate_fn(global_params))
            records.append(RoundRecord(
                round=t, time=float(t), global_acc=acc,
                uploads_so_far=comm.model_uploads,
                selected=[int(i) for i in np.where(mask)[0]],
                values=vals_list,
                client_accs=[float(a) for a in np.asarray(client_accs)]))
            if verbose:
                print(f"[{alg}] round {t:3d} acc={acc:.4f} uploads={comm.model_uploads} "
                      f"selected={int(mask.sum())}/{N}")

    return RunResult(alg, records, comm, run_cfg.target_acc).finalize_target()


# =========================================================== event-driven ===

# module-level jitted composites: built once, reused across runs — repeated
# runs over the same shapes (benchmark sweeps, engine comparisons) hit the
# compile cache instead of re-jitting per run
_mix_jit = jax.jit(async_mix)
_scatter_jit = jax.jit(tree_scatter)
_gather_jit = jax.jit(tree_gather)
# stacking a tuple of pytrees eagerly costs one dispatch per element per
# leaf; under jit it is one compiled concat (retraces only on a new length)
_stack_jit = jax.jit(lambda trees: tree_stack(list(trees)))


@jax.jit
def _flush_mix_jit(g, src, rows, coef, rho_s):
    """FedBuff buffer flush: gather the buffered rows from their stacked
    source, staleness-weighted mean, async-mix — one compiled call.  The
    math is ``aggregation.buffered_mix`` (shared ``buffered_mean`` core);
    only the row gather is fused in here."""
    bar = buffered_mean(tree_gather(src, rows), coef)
    return async_mix(g, bar, rho_s)


@jax.jit
def _apply_downloads_jit(cp, idx, vstack, rel):
    """Window download write-back: every client in ``idx`` receives the
    global model version it downloaded (``vstack[rel]``), one scatter."""
    return jax.tree.map(
        lambda s, v: s.at[idx].set(v[rel].astype(s.dtype)), cp, vstack)


def _event_helpers(run_cfg: FLRunConfig, client_eval_fn, sq_diff):
    """Jitted helpers shared by the sequential loop and the batched engine.
    Both engines route per-client math through the SAME compiled
    executables (vmapped over the window axis; the sequential loop uses
    size-1 stacks), so the batched engine at max_batch=1/buffer_size=1 is
    bit-identical to the per-event loop."""
    try:
        return _event_helpers_cached(run_cfg.num_clients, client_eval_fn,
                                     sq_diff)
    except TypeError:   # unhashable eval/backend: build uncached
        return _build_event_helpers(run_cfg.num_clients, client_eval_fn,
                                    sq_diff)


# small maxsize on purpose: each entry pins its client_eval_fn closure
# (which holds the test set as device arrays) plus the jitted executables
@lru_cache(maxsize=4)
def _event_helpers_cached(num_clients, client_eval_fn, sq_diff):
    return _build_event_helpers(num_clients, client_eval_fn, sq_diff)


def _build_event_helpers(num_clients, client_eval_fn, sq_diff):
    batch_eval = jax.jit(jax.vmap(client_eval_fn))
    values_fn = jax.jit(jax.vmap(
        lambda pg, gc, a: value_lib.communication_value(
            pg, gc, a, num_clients, sq_diff_fn=sq_diff)))
    norms_fn = jax.jit(jax.vmap(tree_sq_norm))
    return batch_eval, values_fn, norms_fn, _mix_jit


@lru_cache(maxsize=8)
def _stale_table(kind: str, size: int = 4096) -> np.ndarray:
    """Vectorized staleness-decay lookup s(tau) for tau in [0, size) —
    one device computation instead of one per upload."""
    return np.asarray(staleness_weight(np.arange(size), kind), np.float64)


def _stale_w(tau: int, kind: str) -> float:
    table = _stale_table(kind)
    if tau < len(table):
        return float(table[tau])
    return float(staleness_weight(tau, kind))


def run_event_driven(run_cfg: FLRunConfig, *, init_params_fn, loss_fn,
                     fed_data, evaluate_fn, client_eval_fn=None,
                     speed: Optional[SpeedModel] = None,
                     verbose: bool = False) -> RunResult:
    """Wall-clock async runtime.  run_cfg.rounds counts *per-client* rounds
    (total events = rounds * N for comparability with round mode).

    ``run_cfg.engine`` selects the execution engine: "sequential" is the
    reference per-event loop (one size-1 jitted update per completion);
    "batched" is the scale engine (stacked client state, windowed vmapped
    execution, buffered mixing — docs/ASYNC_ENGINE.md)."""
    alg = run_cfg.algorithm
    N = run_cfg.num_clients
    client_eval_fn = client_eval_fn or evaluate_fn
    speed = speed or SpeedModel.paper_testbed(N, run_cfg.seed)
    if run_cfg.engine not in ("sequential", "batched"):
        raise ValueError(f"unknown engine: {run_cfg.engine!r}")
    if alg == "fedavg":   # sync barrier is its own runtime (already one
        # vmapped update per round, so both engine values share it)
        return _run_sync_barrier(run_cfg, init_params_fn, loss_fn, fed_data,
                                 evaluate_fn, speed, verbose)
    if run_cfg.engine == "batched":
        return _run_event_batched(run_cfg, init_params_fn, loss_fn, fed_data,
                                  evaluate_fn, client_eval_fn, speed, verbose)
    rng = jax.random.key(run_cfg.seed)
    rng, krng = jax.random.split(rng)
    global_params = init_params_fn(krng)
    comm = CommStats(model_bytes=tree_bytes(global_params))
    codec, bcodec, ef = _make_codecs(run_cfg)
    sq_diff = _value_fn(run_cfg)

    # single-client jitted update (vmapped update over a size-1 stack)
    local_update = make_local_update(loss_fn, run_cfg.local)
    data = {"images": jnp.asarray(fed_data.images),
            "labels": jnp.asarray(fed_data.labels),
            "mask": jnp.asarray(fed_data.mask)}

    # per-client state
    client_params = [global_params] * N
    prev_grads = [None] * N
    known_V = np.full(N, np.inf)      # latest reported V per client
    model_version = np.zeros(N, int)  # version each client last downloaded
    server_version = 0
    prev_global = global_params
    prev_prev_global = global_params

    records: list = []
    total_events = run_cfg.rounds * N
    sched = EventScheduler(N, speed)
    batch_eval, values_fn, norms_fn, mix_fn = _event_helpers(
        run_cfg, client_eval_fn, sq_diff)

    for ev in range(total_events):
        t_now, i = sched.pop()
        rng, urng = jax.random.split(rng)
        one = jax.tree.map(lambda x: x[None], client_params[i])
        d_i = {k: v[i:i + 1] for k, v in data.items()}
        newp_s, eff_s, _ = local_update(one, d_i, urng)
        newp = jax.tree.map(lambda x: x[0], newp_s)
        eff_grad = jax.tree.map(lambda x: x[0], eff_s)

        upload = True
        if alg == "vafl":
            accs = batch_eval(newp_s)
            pg = prev_grads[i] if prev_grads[i] is not None else jax.tree.map(
                jnp.zeros_like, eff_grad)
            pg_s = jax.tree.map(lambda x: x[None], pg)
            V_i = float(values_fn(pg_s, eff_s, accs)[0])
            comm.record_report(1)
            known_V[i] = V_i
            finite = known_V[np.isfinite(known_V)]
            upload = V_i >= finite.mean() if len(finite) else True
        elif alg == "eaflm":
            delta = _tree_delta(prev_global, prev_prev_global)
            thr = float(value_lib.eaflm_threshold([delta], run_cfg.eaflm_alpha,
                                                  run_cfg.eaflm_beta, 1))
            comm.record_report(1)
            upload = float(norms_fn(eff_s)[0]) > thr

        if upload:
            if codec.is_identity:
                recon = newp
                comm.record_upload(1)
            else:
                # ship codec(delta vs the model this client downloaded);
                # the server mixes the reconstruction it actually received
                recon = _compressed_upload(
                    codec, ef, comm, client_params[i], newp, i,
                    _enc_seed(run_cfg, ev, i, _UPLOAD))
            staleness = server_version - model_version[i]
            s = _stale_w(staleness, run_cfg.staleness_kind)
            prev_prev_global = prev_global
            prev_global = global_params
            global_params = mix_fn(global_params, recon, run_cfg.mix_rate * s)
            server_version += 1

        # client downloads the latest global model and goes again
        if bcodec is None:
            client_params[i] = global_params
            comm.record_broadcast(1)
        else:
            client_params[i] = _compressed_broadcast(
                bcodec, comm, global_params, 1,
                _enc_seed(run_cfg, ev, i, _BROADCAST))
        model_version[i] = server_version
        prev_grads[i] = eff_grad
        sched.schedule(i)

        if (ev + 1) % run_cfg.events_per_eval == 0:
            acc = float(evaluate_fn(global_params))
            records.append(RoundRecord(
                round=ev + 1, time=t_now, global_acc=acc,
                uploads_so_far=comm.model_uploads))
            if verbose:
                print(f"[{alg}/event] ev {ev+1:4d} t={t_now:8.1f} acc={acc:.4f} "
                      f"uploads={comm.model_uploads}")

    res = RunResult(alg, records, comm, run_cfg.target_acc).finalize_target()
    res.idle_fraction = float(sched.idle_fraction().mean())
    return res


def _run_event_batched(run_cfg: FLRunConfig, init_params_fn, loss_fn,
                       fed_data, evaluate_fn, client_eval_fn, speed,
                       verbose) -> RunResult:
    """Batched async execution engine (docs/ASYNC_ENGINE.md).

    Per-client state lives in device-resident stacked pytrees (leading
    axis = client) instead of Python lists; each scheduler window of up to
    ``max_batch`` completions runs as ONE vmapped jitted local update over
    the gathered sub-stack, and accepted uploads flow through a
    FedBuff-style buffer flushed as a staleness-weighted mean every
    ``buffer_size`` arrivals.  Gating semantics: per-client decisions are
    applied in arrival order within the window; the EAFLM server-delta
    threshold is evaluated once per window (at the mix point).  The
    compression plumbing is unchanged — codec payloads and error feedback
    stay per-client."""
    alg = run_cfg.algorithm
    N = run_cfg.num_clients
    rng = jax.random.key(run_cfg.seed)
    rng, krng = jax.random.split(rng)
    global_params = init_params_fn(krng)
    comm = CommStats(model_bytes=tree_bytes(global_params))
    codec, bcodec, ef = _make_codecs(run_cfg)
    sq_diff = _value_fn(run_cfg)

    local_update = make_local_update(loss_fn, run_cfg.local)
    data = {"images": jnp.asarray(fed_data.images),
            "labels": jnp.asarray(fed_data.labels),
            "mask": jnp.asarray(fed_data.mask)}

    # device-resident stacked per-client state — the tentpole: no Python
    # lists of full pytrees, everything gathers/scatters on a leading axis
    client_params = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (N,) + x.shape), global_params)
    prev_grads = jax.tree.map(
        lambda x: jnp.zeros((N,) + x.shape, jnp.float32), global_params)
    known_V = np.full(N, np.inf)      # latest reported V per client
    model_version = np.zeros(N, int)  # version each client last downloaded
    server_version = 0
    prev_global = global_params
    prev_prev_global = global_params

    batch_eval, values_fn, norms_fn, mix_fn = _event_helpers(
        run_cfg, client_eval_fn, sq_diff)

    W = run_cfg.max_batch if run_cfg.max_batch > 0 else N
    W = max(1, min(W, N))
    K = max(1, run_cfg.buffer_size)
    total_events = run_cfg.rounds * N
    sched = EventScheduler(N, speed)
    records: list = []
    # the FedBuff buffer: (stacked_tree, row) references — rows of the
    # window's vmapped output for identity uploads, size-1 stacks for
    # codec reconstructions; gathered/stacked only at flush time
    buffer: list = []
    buf_stale: list = []              # their staleness weights s(tau)

    def flush():
        nonlocal global_params, prev_global, prev_prev_global, server_version
        prev_prev_global = prev_global
        prev_global = global_params
        if len(buffer) == 1:          # bit-exact sequential mix (K=1 path)
            ref, row = buffer[0]
            global_params = buffered_mix(
                global_params, [stacked_index(ref, row)], buf_stale,
                run_cfg.mix_rate, mix=mix_fn)
        else:
            groups: list = []         # consecutive same-source rows
            for ref, row in buffer:
                if groups and groups[-1][0] is ref:
                    groups[-1][1].append(row)
                else:
                    groups.append((ref, [row]))
            if len(groups) == 1:      # common case: one source, jitted gather
                src, rows = groups[0]
            else:                     # buffer spans windows/codec payloads
                src = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, 0),
                    *[tree_gather(ref, np.asarray(rows))
                      for ref, rows in groups])
                rows = range(len(buffer))
            coef, rho_sbar = buffered_coefs(buf_stale, run_cfg.mix_rate)
            global_params = _flush_mix_jit(
                global_params, src, np.asarray(rows, np.int32), coef,
                rho_sbar)
        server_version += 1
        buffer.clear()
        buf_stale.clear()

    ev = 0
    while ev < total_events:
        times, idx_np = sched.pop_window(min(W, total_events - ev))
        t_now = float(times[-1])
        w = len(idx_np)
        idx = jnp.asarray(idx_np)
        rng, urng = jax.random.split(rng)
        sub_base = _gather_jit(client_params, idx)     # the downloaded models
        d_w = _gather_jit(data, idx)
        newp, eff, _ = local_update(sub_base, d_w, urng)

        V_w = norms_w = None
        thr = 0.0
        if alg == "vafl":
            accs = batch_eval(newp)
            V_w = np.asarray(
                values_fn(_gather_jit(prev_grads, idx), eff, accs),
                np.float64)
        elif alg == "eaflm":
            # server deltas are frozen between mix points, so the Eq. 3
            # threshold is evaluated once per window
            delta = _tree_delta(prev_global, prev_prev_global)
            thr = float(value_lib.eaflm_threshold([delta], run_cfg.eaflm_alpha,
                                                  run_cfg.eaflm_beta, 1))
            norms_w = np.asarray(norms_fn(eff), np.float64)

        dl_rel = np.empty(w, np.int64)      # per-event index into ver_trees
        ver_trees: list = []                # distinct globals downloaded
        ver_pos: dict = {}                  # server_version -> position
        enc_downloads: list = []            # per-client lossy downlink trees
        for j in range(w):
            i = int(idx_np[j])
            upload = True
            if alg == "vafl":
                comm.record_report(1)
                V_i = float(V_w[j])
                known_V[i] = V_i
                finite = known_V[np.isfinite(known_V)]
                upload = V_i >= finite.mean() if len(finite) else True
            elif alg == "eaflm":
                comm.record_report(1)
                upload = float(norms_w[j]) > thr

            if upload:
                if codec.is_identity:
                    buffer.append((newp, j))
                    comm.record_upload(1)
                else:
                    recon = _compressed_upload(
                        codec, ef, comm, stacked_index(sub_base, j),
                        stacked_index(newp, j), i,
                        _enc_seed(run_cfg, ev + j, i, _UPLOAD))
                    buffer.append((jax.tree.map(lambda x: x[None], recon), 0))
                buf_stale.append(_stale_w(server_version - model_version[i],
                                          run_cfg.staleness_kind))
                if len(buffer) >= K:
                    flush()

            if bcodec is None:
                comm.record_broadcast(1)
                if server_version not in ver_pos:
                    ver_pos[server_version] = len(ver_trees)
                    ver_trees.append(global_params)
                dl_rel[j] = ver_pos[server_version]
            else:
                enc_downloads.append(_compressed_broadcast(
                    bcodec, comm, global_params, 1,
                    _enc_seed(run_cfg, ev + j, i, _BROADCAST)))
            model_version[i] = server_version
            # restart from the client's own completion time — window
            # execution must not barrier the simulated clock
            sched.schedule(i, start=times[j])

        if any(ref is newp for ref, _ in buffer):
            # detach leftover buffer entries from the W-wide window output
            # before it goes out of scope: under gating a partially-full
            # buffer would otherwise pin one full (W, ...) stack per window
            # until the flush — gather just the buffered rows instead
            rows = np.asarray([r for ref, r in buffer if ref is newp])
            sub = tree_gather(newp, rows)
            fresh = iter(range(len(rows)))
            buffer[:] = [(sub, next(fresh)) if ref is newp else (ref, r)
                         for ref, r in buffer]

        # write the window back in one jitted call each: downloads gather
        # from the stack of distinct globals, prev eff-grads scatter direct.
        # The version count varies per window under gating, so the stack is
        # padded to the next power of two — O(log W) compiled variants
        # instead of one per distinct count (padding rows are never indexed)
        if bcodec is None:
            if len(ver_trees) > 1:
                bucket = 1 << (len(ver_trees) - 1).bit_length()
                padded = ver_trees + [ver_trees[-1]] * (bucket
                                                        - len(ver_trees))
                vstack = _stack_jit(tuple(padded))
            else:
                vstack = jax.tree.map(lambda x: x[None], ver_trees[0])
            client_params = _apply_downloads_jit(client_params, idx, vstack,
                                                 jnp.asarray(dl_rel))
        else:
            client_params = _scatter_jit(client_params, idx,
                                         _stack_jit(tuple(enc_downloads)))
        prev_grads = _scatter_jit(prev_grads, idx, eff)

        prev_ev, ev = ev, ev + w
        epe = run_cfg.events_per_eval
        if ev // epe > prev_ev // epe:
            acc = float(evaluate_fn(global_params))
            records.append(RoundRecord(round=ev, time=t_now, global_acc=acc,
                                       uploads_so_far=comm.model_uploads))
            if verbose:
                print(f"[{alg}/batched] ev {ev:5d} t={t_now:8.1f} "
                      f"acc={acc:.4f} uploads={comm.model_uploads}")

    if buffer:  # partial buffer at run end — flush so no update is lost
        flush()

    res = RunResult(alg, records, comm, run_cfg.target_acc).finalize_target()
    res.idle_fraction = float(sched.idle_fraction().mean())
    return res


def _run_sync_barrier(run_cfg, init_params_fn, loss_fn, fed_data, evaluate_fn,
                      speed, verbose):
    """Synchronous FedAvg with a round barrier — the idle-time baseline.
    Honors the same codec config as the async runtimes (uploads ship
    codec(delta vs the broadcast base) with error feedback) and the same
    ``participation`` fraction as the round-based runtime: each round only
    the sampled set S trains/uploads, the barrier waits for the slowest
    *participant*, and non-participants sit idle."""
    N = run_cfg.num_clients
    rng = jax.random.key(run_cfg.seed)
    rng, krng = jax.random.split(rng)
    global_params = init_params_fn(krng)
    comm = CommStats(model_bytes=tree_bytes(global_params))
    codec, bcodec, ef = _make_codecs(run_cfg)
    client_base = global_params
    local_update = make_local_update(loss_fn, run_cfg.local)
    data = {"images": jnp.asarray(fed_data.images),
            "labels": jnp.asarray(fed_data.labels),
            "mask": jnp.asarray(fed_data.mask)}
    counts = jnp.asarray(fed_data.counts, jnp.float32)
    records = []
    now = 0.0
    busy = np.zeros(N)
    part_rng = np.random.RandomState(run_cfg.seed + 101)
    for t in range(1, run_cfg.rounds + 1):
        rng, urng = jax.random.split(rng)
        # the round's participating set S (same sampling as round-based)
        part = _participation_mask(part_rng, run_cfg.participation, N)
        stacked = jax.tree.map(lambda x: jnp.broadcast_to(x, (N,) + x.shape),
                               client_base)
        stacked, _, _ = local_update(stacked, data, urng)
        round_times = np.array([speed.sample(c) for c in range(N)])
        now += round_times[part].max()    # barrier: slowest *participant*
        busy[part] += round_times[part]   # non-participants idle all round
        sel = [int(i) for i in np.flatnonzero(part)]
        if codec.is_identity:
            comm.record_upload(len(sel))
        else:
            recon = [_compressed_upload(codec, ef, comm, client_base,
                                        stacked_index(stacked, i), i,
                                        _enc_seed(run_cfg, t, i, _UPLOAD))
                     for i in sel]
            stacked = tree_scatter(stacked, jnp.asarray(sel),
                                   tree_stack(recon))
        global_params = aggregate_or_keep(global_params, stacked,
                                          jnp.asarray(part), counts)
        if bcodec is None:
            comm.record_broadcast(N)
            client_base = global_params
        else:
            client_base = _compressed_broadcast(
                bcodec, comm, global_params, N,
                _enc_seed(run_cfg, t, 0, _BROADCAST))
        if t % run_cfg.eval_every == 0:
            acc = float(evaluate_fn(global_params))
            records.append(RoundRecord(round=t, time=now, global_acc=acc,
                                       uploads_so_far=comm.model_uploads))
            if verbose:
                print(f"[fedavg] round {t:3d} t={now:8.1f} acc={acc:.4f}")
    res = RunResult("fedavg", records, comm, run_cfg.target_acc).finalize_target()
    res.idle_fraction = float(1.0 - (busy / max(now, 1e-9)).mean())
    return res
