"""Plumbing shared by the four FL runtimes (rounds / events / batched /
sync): codec wiring with per-client error feedback, deterministic
per-transfer encode seeds, participation sampling, and the memoized
jitted helper set the event runtimes route per-client math through.

Nothing in here knows which algorithm is running — runtimes consume the
``UploadPolicy`` / ``Aggregator`` protocol (repro.algorithms) for every
algorithm-dependent decision.
"""
from __future__ import annotations

from contextlib import nullcontext
from functools import lru_cache, partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.common.pytree import (stacked_index, tree_gather, tree_scatter,
                                 tree_stack, tree_sq_norm)
from repro.compress import (DeviceWire, ErrorFeedback, RowDelta,
                            compress_update, get_codec, wire_nbytes)
from repro.core import value as value_lib


def _value_fn(cfg):
    if cfg.value_backend is not None:
        return cfg.value_backend
    from repro.common.pytree import tree_sq_diff_norm
    return tree_sq_diff_norm


# ------------------------------------------------- compression plumbing ---

def _make_codecs(run_cfg):
    codec = get_codec(run_cfg.compressor)
    bcodec = None
    if run_cfg.broadcast_compressor not in (None, "", "identity", "none"):
        bcodec = get_codec(run_cfg.broadcast_compressor)
    return codec, bcodec, ErrorFeedback(enabled=run_cfg.error_feedback)


_UPLOAD, _BROADCAST = 1, 2


# ------------------------------------------------- obs plumbing ---

def _obs_for_run(run_cfg):
    """The run's ``repro.obs`` Observer, or None when observability is
    off (``obs=None``, the default) — every hook site in the runtimes is
    behind an ``if obs is not None`` so the disabled path costs one
    branch, nothing else (docs/OBSERVABILITY.md)."""
    ocfg = getattr(run_cfg, "obs", None)
    if ocfg is None:
        return None
    from repro.obs import Observer
    return Observer(ocfg, meta={
        "algorithm": run_cfg.algorithm, "engine": run_cfg.engine,
        "num_clients": run_cfg.num_clients, "seed": run_cfg.seed,
        "compressor": run_cfg.compressor,
        "broadcast_compressor": run_cfg.broadcast_compressor})


def _finish_obs(res, obs):
    """Seal the observer onto the result (exports + metrics snapshot)."""
    if obs is not None:
        obs.finish(res)
    return res


def _span(obs, name, **kw):
    """``obs.span(name, ...)``, or a do-nothing context when obs is off."""
    return nullcontext() if obs is None else obs.span(name, **kw)


def _annotate(obs, name, **tags):
    """``obs.annotate(name, ...)`` (the profiler half of a span, for a
    block whose record a hook writes), or nothing when obs is off."""
    return nullcontext() if obs is None else obs.annotate(name, **tags)


# ------------------------------------------------- scenario plumbing ---

def _scenario_models(run_cfg, num_clients):
    """Build the run's ``repro.sim`` scenario models: ``(compute,
    network, availability)``, or ``(None, None, None)`` for the default
    scenario — ``scenario=None`` *or* an all-defaults config (the
    ``"default"`` zoo entry) — the bit-exact legacy path."""
    if run_cfg.scenario is None or run_cfg.scenario.is_default():
        return None, None, None
    return run_cfg.scenario.build(num_clients, run_cfg.seed)


def _active(model):
    """A scenario model that is present and not a declared no-op
    (ideal network / always-on availability carry ``active = False``)."""
    return model is not None and getattr(model, "active", True)


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


def _enc_seed(run_cfg, step: int, i: int, kind: int) -> int:
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


@jax.jit
def upload_reconstruction(base, brow, decoded):
    """Row ``brow`` of the stacked ``base`` plus the decoded delta: the
    model the server received."""
    return _tree_apply_delta(stacked_index(base, brow), decoded)


def _compressed_upload(codec, ef, base, brow, client, crow, i, seed,
                       obs=None, device=None):
    """One client's compressed upload: encode codec(delta vs row ``brow``
    of ``base``, the model the client downloaded, from row ``crow`` of
    the stacked ``client`` trees) with error feedback, and return (the
    reconstruction the server actually receives, the wire).  Where the
    codec has a device round trip, nothing here reads the device: the
    caller reads the wires' bytes (``wire_nbytes``) once its decisions are
    made.  ``device`` (sharded client state) moves the delta, and so the
    client's error-feedback residual, onto that one device first: a
    Pallas codec kernel cannot be partitioned across devices.
    Under obs the codec's work is a host-timed "encode" span tagged
    with the codec."""
    update = RowDelta(base, brow, client, crow)
    if device is not None:
        update = jax.device_put(update.tree(), device)
    with (obs.timed("encode", client=i, codec=codec.name)
          if obs is not None else nullcontext()):
        wire, decoded = compress_update(codec, ef, i, update, seed=seed)
    if device is not None:
        # back beside the base: replicated over the devices that hold it
        held = jax.tree.leaves(base)[0].sharding
        decoded = jax.device_put(decoded, NamedSharding(held.mesh,
                                                        PartitionSpec())
                                 if hasattr(held, "mesh") else held)
    return upload_reconstruction(base, brow, decoded), wire


def _compressed_broadcast(bcodec, comm, params, n, seed, obs=None):
    """Encode one model broadcast to ``n`` clients; returns the lossy
    model they actually receive (no EF on the downlink — clients train
    from what arrived)."""
    with (obs.timed("encode", codec=bcodec.name, broadcast=True)
          if obs is not None else nullcontext()):
        bp = bcodec.encode(params, seed=seed)
        out = bcodec.decode(bp)
    comm.record_broadcast(n, nbytes=n * bp.nbytes)
    return out


def _round_uploads(run_cfg, codec, ef, comm, base, stacked, mask, t,
                   up_acc=None, obs=None, sim=None):
    """One synchronous round's upload leg, shared by the round-based and
    sync-barrier runtimes: account the selected set's uploads; with a
    codec, each selected client ships codec(delta vs ``base``, its
    download) with error feedback and the reconstructions are scattered
    back into the stack (the server aggregates what it received).
    ``up_acc`` (optional (N,) int array) receives each client's actual
    on-the-wire upload bytes — the scenario clock's input.  Under obs
    each selected client's upload becomes a trace event (staleness is 0
    by construction: synchronous rounds aggregate fresh models)."""
    sel = [int(i) for i in np.flatnonzero(mask)]
    if codec.is_identity:
        comm.record_upload(len(sel))
        for i in sel:
            if up_acc is not None:
                up_acc[i] += comm.model_bytes
            if obs is not None:
                obs.upload(i, sim, nbytes=comm.model_bytes, codec=codec.name)
        return stacked
    base1 = jax.tree.map(lambda x: x[None], base)
    recon, wires = [], []
    for i in sel:
        r, wire = _compressed_upload(codec, ef, base1, 0, stacked, i, i,
                                     _enc_seed(run_cfg, t, i, _UPLOAD),
                                     obs=obs)
        recon.append(r)
        wires.append(wire)
    for i, wire, nbytes in zip(sel, wires, wire_nbytes(wires)):
        comm.record_upload(1, nbytes=nbytes)
        if up_acc is not None:
            up_acc[i] += nbytes
        if obs is not None:
            obs.upload(i, sim, nbytes=nbytes, codec=codec.name,
                       device_codec=isinstance(wire, DeviceWire))
    if sel:   # one scatter per leaf, not one stack copy per client
        stacked = tree_scatter(stacked, jnp.asarray(sel), tree_stack(recon))
    return stacked


def _round_broadcast(run_cfg, bcodec, comm, global_params, n, t,
                     down_acc=None, obs=None, sim=None):
    """One synchronous round's broadcast leg: returns the model the
    clients actually receive (lossy under a downlink codec).  ``down_acc``
    (optional (n,) int array) receives each client's downlink bytes.
    Under obs the whole round's broadcast is ONE trace event with n
    receivers and the TOTAL wire bytes."""
    if bcodec is None:
        comm.record_broadcast(n)
        if down_acc is not None:
            down_acc += comm.model_bytes
        if obs is not None:
            obs.broadcast(None, sim, nbytes=n * comm.model_bytes, n=n)
        return global_params
    d0 = comm.downlink_bytes
    out = _compressed_broadcast(bcodec, comm, global_params, n,
                                _enc_seed(run_cfg, t, 0, _BROADCAST),
                                obs=obs)
    if down_acc is not None:
        down_acc += (comm.downlink_bytes - d0) // n
    if obs is not None:
        obs.broadcast(None, sim, nbytes=comm.downlink_bytes - d0, n=n,
                      codec=bcodec.name)
    return out


def _flush_reconstructions(aggregator, global_params, recons, stales):
    """Mix a buffer of reconstruction trees into the global model — the
    FedBuff-K commit shared by the serve loop (``repro.serve.server``,
    which ingests its windows from an external upload queue) and any
    engine holding materialised reconstructions.  A singleton buffer is
    the sequential per-arrival mix bit for bit (``buffered_mix`` K=1
    path); larger buffers take the aggregator's ``flush_mix`` so a
    plugin aggregator stays in charge of its own mixing."""
    from repro.core.aggregation import buffered_coefs, buffered_mix
    if len(recons) == 1:
        return buffered_mix(global_params, recons, stales,
                            aggregator.mix_rate, mix=aggregator.mix)
    src = tree_stack(list(recons))
    coef, rho_sbar = buffered_coefs(stales, aggregator.mix_rate)
    return aggregator.flush_mix(global_params, src,
                                np.arange(len(recons), dtype=np.int32),
                                coef, rho_sbar)


def _attach_sim_result(res, sched):
    """Copy the scheduler's per-client simulation ledger onto a
    ``RunResult`` (event-driven runtimes, both engines)."""
    idle = sched.idle_fraction()
    res.sim_time = float(sched.now)
    res.idle_fraction = float(idle.mean())
    res.client_idle = [float(x) for x in idle]
    res.client_uplink_bytes = [int(x) for x in sched.client_up_bytes]
    res.client_downlink_bytes = [int(x) for x in sched.client_down_bytes]
    res.client_failed_rounds = [int(x) for x in sched.client_failed_rounds]
    return res


# ----------------------------------------------- jitted event-path helpers ---

# ------------------------------------------- batched-engine jit set ---

def _fold_flush(gp, src, rows, coef, rho_sbar):
    """The FedBuff flush math (== aggregation.flush_mix_jit) as a plain
    traceable function, so the window-commit jits can fold the window's
    final flush into the same compiled call as the download write-back."""
    from repro.core.aggregation import async_mix, buffered_mean
    bar = buffered_mean(tree_gather(src, rows), coef)
    return async_mix(gp, bar, rho_sbar)


def _append_version(vstack, gnew):
    """Extend the stacked download-version trees with the in-jit flushed
    global (the version clients downloading AFTER the folded flush see)."""
    return jax.tree.map(
        lambda v, g: jnp.concatenate([v, g[None].astype(v.dtype)], 0),
        vstack, gnew)


@lru_cache(maxsize=8)
def _engine_jits(sharding):
    """The batched engine's compiled helper set, built once per client
    sharding (``None`` = unsharded single-host).  Everything that writes
    the big (N, ...) stacked state donates it (``donate_argnums``) — at
    N=1024 a non-donated scatter doubles peak memory for client_params
    every window — and constrains its stacked outputs back onto the
    client sharding so updates never silently migrate to one device.
    Cached on the sharding so benchmark sweeps reuse executables."""
    nshard = 1 if sharding is None else int(sharding.mesh.devices.size)

    def _cons(x):
        # divisibility-guarded, like sharding.spec_for: odd-sized window
        # sub-stacks stay wherever XLA put them
        if sharding is None or x.ndim == 0 or x.shape[0] % nshard:
            return x
        return jax.lax.with_sharding_constraint(x, sharding)

    def cons(tree):
        return jax.tree.map(_cons, tree)

    # named functions, so that each program has its own name in a trace
    @jax.jit
    def gather_rows(s, i):
        return cons(tree_gather(s, i))

    # NOT constrained: stack() builds the download-version stack, whose
    # leading dim is versions, not clients — constraining it whenever the
    # version count happened to divide the device count would spread the
    # versions across devices and turn every commit's v[rel] gather into
    # an all-gather.  Client-axis stacks go through place() explicitly.
    @jax.jit
    def stack_trees(trees):
        return tree_stack(list(trees))

    place = jax.jit(cons)

    @partial(jax.jit, donate_argnums=(0, 1))
    def commit_win(cp, pg, idx, vstack, rel, eff):
        """Sub-full-window commit: downloads gather from the stack of
        distinct global versions and scatter into ``cp``; the window's
        effective gradients scatter into ``pg`` — one call, both stacked
        buffers donated."""
        cp = jax.tree.map(
            lambda s, v: s.at[idx].set(v[rel].astype(s.dtype)), cp, vstack)
        pg = jax.tree.map(lambda s, u: s.at[idx].set(u), pg, eff)
        return cons(cp), cons(pg)

    @partial(jax.jit, donate_argnums=(1, 2))
    def commit_win_flush(gp, cp, pg, idx, vstack, rel, eff,
                         src, rows, coef, rho_sbar):
        """commit_win with the window's final buffer flush folded in:
        the new global is produced and applied to the clients that
        downloaded it (rel == len(vstack)) inside the same executable."""
        gnew = _fold_flush(gp, src, rows, coef, rho_sbar)
        vx = _append_version(vstack, gnew)
        cp = jax.tree.map(
            lambda s, v: s.at[idx].set(v[rel].astype(s.dtype)), cp, vx)
        pg = jax.tree.map(lambda s, u: s.at[idx].set(u), pg, eff)
        return gnew, cons(cp), cons(pg)

    @jax.jit
    def commit_full(vstack, rel, eff):
        """Full-window commit (w == N): every client downloaded, so the
        write-back is a pure per-client gather of download versions — no
        scatter, no donation needed (the old stacks are simply dropped);
        prev_grads IS the window's eff stack (client order)."""
        return cons(jax.tree.map(lambda v: v[rel], vstack)), cons(eff)

    @jax.jit
    def commit_full_flush(gp, vstack, rel, eff, src, rows, coef, rho_sbar):
        gnew = _fold_flush(gp, src, rows, coef, rho_sbar)
        vx = _append_version(vstack, gnew)
        return gnew, cons(jax.tree.map(lambda v: v[rel], vx)), cons(eff)

    @partial(jax.jit, donate_argnums=(0,))
    def scatter_donated(s, idx, rows):
        """Donated tree_scatter for the lossy-downlink (bcodec) path."""
        return cons(tree_scatter(s, idx, rows))

    return SimpleNamespace(
        gather=gather_rows, stack=stack_trees, place=place,
        commit_win=commit_win,
        commit_win_flush=commit_win_flush, commit_full=commit_full,
        commit_full_flush=commit_full_flush, scatter_donated=scatter_donated)


# clients whose accuracy scans run at once: the per-client evaluator
# scans the whole test set, and vmapped over a window every scan step
# holds rows x eval_batch model activations.  The CNN at eval_batch 500
# needs ~64 MB of scratch per client (ahead-of-time compile for v5e), so
# 64 rows take ~4.2 GB where a 256-client window at once took ~16.4 GB,
# more than a v5e chip's 16 GB.
_EVAL_ROWS = 64


def _client_eval_vmap(client_eval_fn):
    """``jax.vmap(client_eval_fn)`` over a stacked pytree, evaluating at
    most ``_EVAL_ROWS`` clients at a time: a window of w > rows clients
    runs as a scan of ceil(w / rows) steps.  Step s takes clients
    a * steps + s, so under client sharding every step spreads over all
    devices (the reshape keeps the leading axis's device blocks).
    Padding rows (a window that ``rows`` does not divide) repeat client
    0 and are dropped.  Used by the two helper sets below."""
    rows = _EVAL_ROWS
    # flcheck: ignore[jit-in-hot-path]
    vf = jax.vmap(client_eval_fn)

    def run(stack):
        w = jax.tree.leaves(stack)[0].shape[0]
        if w <= rows:
            return vf(stack)
        steps = -(-w // rows)
        pad = steps * rows - w

        def split(x):
            if pad:
                x = jnp.concatenate(
                    [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])
            return x.reshape((rows, steps) + x.shape[1:]).swapaxes(0, 1)

        out = jax.lax.map(vf, jax.tree.map(split, stack))
        return jax.tree.map(
            lambda y: y.swapaxes(0, 1).reshape((-1,) + y.shape[2:])[:w], out)
    return run


def _round_helpers(run_cfg, client_eval_fn):
    """Jitted stacked round inputs shared by the round-based and
    sync-barrier runtimes: per-client eval, Eq. 1 values, grad norms.
    All are lazy jits — nothing compiles unless the policy (or the
    round record) actually reads the input."""
    sq_diff = _value_fn(run_cfg)
    N = run_cfg.num_clients
    # intentionally per-run (not memoized): the round/sync runtimes call
    # this once per run and the closures capture run-specific N/sq_diff;
    # caching would pin the eval fn's device arrays past the run
    # flcheck: ignore[jit-in-hot-path]
    batch_eval = jax.jit(_client_eval_vmap(client_eval_fn))

    def communication_values(gp, gc, accs):
        return value_lib.communication_values_stacked(gp, gc, accs, N,
                                                      sq_diff_fn=sq_diff)

    # flcheck: ignore[jit-in-hot-path]
    values_fn = jax.jit(communication_values)
    # flcheck: ignore[jit-in-hot-path]
    grad_norms_fn = jax.jit(jax.vmap(tree_sq_norm))
    return batch_eval, values_fn, grad_norms_fn


def _event_helpers(run_cfg, client_eval_fn, sq_diff):
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
    # memoized by the caller (_event_helpers_cached wraps this in
    # lru_cache; the direct call is the documented unhashable-eval
    # fallback), so the zero-recompile-rerun contract holds
    # flcheck: ignore[jit-in-hot-path]
    batch_eval = jax.jit(_client_eval_vmap(client_eval_fn))

    def communication_value(pg, gc, a):
        return value_lib.communication_value(pg, gc, a, num_clients,
                                             sq_diff_fn=sq_diff)

    # flcheck: ignore[jit-in-hot-path]
    values_fn = jax.jit(jax.vmap(communication_value))
    # flcheck: ignore[jit-in-hot-path]
    norms_fn = jax.jit(jax.vmap(tree_sq_norm))
    return batch_eval, values_fn, norms_fn
