"""Client workers: the other end of a serve transport.

Three drivers share one compute bundle (:class:`ClientCompute` — the
SAME memoized jitted executables the closed-loop runtimes use, so a
serve run compiles nothing new):

* :class:`ThreadClientWorker` — a free-running thread per client:
  local round -> (report ->) upload -> download, repeatedly, optionally
  paced by a ``repro.sim`` speed model (:class:`ScenarioPacer`).
  Concurrency is real: arrival order at the server is whatever the
  threads produce.

* :class:`SequentialDriver` — the determinism bridge.  One thread owns
  every client AND pumps the server between sends, replicating the
  sequential event loop's RNG chain, scheduler arithmetic and encode
  seeds exactly — a ``buffer_size=1`` serve run through this driver is
  bit-identical to the closed-loop engines (tests/test_algorithms.py).

* :class:`ProcessClientWorker` — a spawned OS process talking to a
  ``socket`` transport (single-phase algorithms; loud error otherwise —
  the Eq. 1 value term needs the server-side eval set).

Wire discipline shared by all drivers: ``seq`` increments on every
message a client sends (the server asserts per-client FIFO on it), and
``version`` echoes the last download so the server's staleness metadata
can be cross-checked.
"""
from __future__ import annotations

import multiprocessing
import os
import threading
import time
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp

from repro.compress import ErrorFeedback, compress_payload, get_codec
from repro.core.runtimes.common import (_enc_seed, _event_helpers,
                                        _tree_delta, _value_fn, _UPLOAD)
from repro.core.client import make_local_update
from repro.serve import messages as wire
from repro.serve.messages import BroadcastMsg, UploadMsg
from repro.serve.socket_transport import _SocketChannel


def _unstack(tree_s):
    return jax.tree.map(lambda x: x[0], tree_s)


class ClientCompute:
    """The per-client math, shared across workers in one process: the
    vmapped local update over size-1 stacks plus the lazily-built scalar
    helpers (Eq. 1 values / grad norms).  Routing through
    ``make_local_update`` / ``_event_helpers`` hits the closed-loop
    runtimes' memo caches, so serve and simulation share executables."""

    def __init__(self, *, loss_fn, local, data, num_clients,
                 client_eval_fn=None, sq_diff=None):
        self.local_update = make_local_update(loss_fn, local)
        self.data = {k: jnp.asarray(v) for k, v in data.items()}
        self._num_clients = num_clients
        self._client_eval_fn = client_eval_fn
        self._sq_diff = sq_diff
        self._helpers = None
        self._norms_only = None

    @classmethod
    def for_run(cls, run_cfg, *, loss_fn, fed_data, client_eval_fn=None):
        return cls(loss_fn=loss_fn, local=run_cfg.local,
                   data={"images": fed_data.images,
                         "labels": fed_data.labels,
                         "mask": fed_data.mask},
                   num_clients=run_cfg.num_clients,
                   client_eval_fn=client_eval_fn,
                   sq_diff=_value_fn(run_cfg))

    def helpers(self):
        if self._helpers is None:
            if self._client_eval_fn is None:
                raise ValueError(
                    "this worker's policy reads Eq. 1 values, which need "
                    "a client eval fn — pass client_eval_fn/evaluate_fn "
                    "to ClientCompute (process workers support "
                    "single-phase algorithms only)")
            self._helpers = _event_helpers(
                SimpleNamespace(num_clients=self._num_clients),
                self._client_eval_fn, self._sq_diff)
        return self._helpers

    def local_round(self, params, i, urng):
        """One client's local round as a size-1 stacked dispatch; returns
        (stacked new params, stacked effective gradient)."""
        one = jax.tree.map(lambda x: x[None], params)
        d_i = {k: v[i:i + 1] for k, v in self.data.items()}
        newp_s, eff_s, _ = self.local_update(one, d_i, urng)
        return newp_s, eff_s

    def value(self, newp_s, eff_s, prev_grad) -> float:
        """Eq. 1 V for this round (policies with ``needs_values``) —
        the exact closed-loop arithmetic including the zeros prev-grad
        bootstrap on a client's first round."""
        batch_eval, values_fn, _ = self.helpers()
        accs = batch_eval(newp_s)
        pg = (prev_grad if prev_grad is not None
              else jax.tree.map(jnp.zeros_like, _unstack(eff_s)))
        pg_s = jax.tree.map(lambda x: x[None], pg)
        return float(values_fn(pg_s, eff_s, accs)[0])

    def norm(self, eff_s) -> float:
        if self._client_eval_fn is not None:
            return float(self.helpers()[2](eff_s)[0])
        # norm-only worker (process path): no eval fn required, so skip
        # the full helper set and jit the norm alone (once)
        if self._norms_only is None:
            from repro.common.pytree import tree_sq_norm
            self._norms_only = jax.jit(jax.vmap(tree_sq_norm))
        return float(self._norms_only(eff_s)[0])


class ScenarioPacer:
    """Paces free-running workers from a ``repro.sim`` speed model: each
    round draws the client's simulated service time, advances that
    client's sim clock (the ``sim_time`` it stamps on uploads) and —
    when ``time_scale > 0`` — sleeps ``time_scale`` host-seconds per
    simulated second (capped) so traffic *shape* follows the scenario
    without replaying it in real time."""

    def __init__(self, speed, time_scale: float = 0.0,
                 max_sleep: float = 0.25):
        self.speed = speed
        self.time_scale = time_scale
        self.max_sleep = max_sleep
        self._t = {}

    def advance(self, client: int) -> float:
        t0 = self._t.get(client, 0.0)
        service = float(self.speed.sample(client, t0))
        self._t[client] = t0 + service
        if self.time_scale > 0:
            time.sleep(min(service * self.time_scale, self.max_sleep))
        return self._t[client]


# ------------------------------------------------------- worker loop ---

def _recv_ctrl(channel, timeout: float, stop=None, skip_init: bool = False):
    """Wait for the server's next broadcast, polling so a stop flag (or
    a dead server) can break the wait; None on deadline.  ``skip_init``
    drops stray mid-run INIT frames (a server that re-admitted this
    client as fresh) instead of returning them as an exchange reply."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if stop is not None and stop.is_set():
            return None
        msg = channel.recv(timeout=0.05)
        if msg is not None:
            if skip_init and msg.kind == wire.INIT:
                continue
            return msg
    return None


def _exchange(channel, msg, *, recv_timeout: float, stop=None,
              retry=None, stats=None):
    """One stop-and-wait exchange: send ``msg``, wait for its reply.

    Without a :class:`~repro.resilience.RetryPolicy` this is a single
    send + wait (the pre-resilience behavior).  With one, the SAME
    frame (same ``seq``) is re-sent with exponential backoff + seeded
    jitter whenever the per-attempt reply wait times out — the server
    dedups by ``(client, seq)`` and replays its cached reply, so
    at-least-once sending composes into exactly-once processing.
    Replies are matched on ``ack_seq``: a stale reply from an earlier
    attempt of a PREVIOUS exchange (the original arrived late, after
    its retry was already answered) is discarded, not misread as this
    exchange's answer.  Returns the reply, or None on exhaustion."""
    attempts = 1 if retry is None else retry.max_attempts
    wait = recv_timeout if retry is None else retry.attempt_timeout_s
    for attempt in range(1, attempts + 1):
        if stop is not None and stop.is_set():
            return None
        if attempt > 1:
            if stats is not None:
                stats["retries"] = stats.get("retries", 0) + 1
            time.sleep(retry.backoff(attempt - 1, msg.client, msg.seq))
        if not channel.send(msg, timeout=recv_timeout):
            continue                   # backpressure deadline: retry
        deadline = time.monotonic() + wait
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                break
            reply = _recv_ctrl(channel, left, stop, skip_init=True)
            if reply is None:
                break
            if (reply.kind in (wire.DECISION, wire.DOWNLOAD)
                    and reply.ack_seq >= 0 and reply.ack_seq != msg.seq):
                continue               # stale reply: keep waiting
            return reply
    return None


def _client_loop(compute: ClientCompute, channel, client: int, *,
                 data_index: Optional[int] = None, pacer=None,
                 rounds: Optional[int] = None, recv_timeout: float = 30.0,
                 stop=None, retry=None, stats=None) -> int:
    """The free-running client body shared by thread and process
    workers; returns the number of completed rounds.  ``retry`` (a
    ``repro.resilience.RetryPolicy``) makes every exchange survive
    lost frames and lost replies; ``stats`` (a dict) accumulates the
    retry count for end-of-run reconciliation."""
    init = _recv_ctrl(channel, recv_timeout, stop)
    if init is None or init.kind != wire.INIT:
        return 0
    meta = init.meta
    params = init.tree
    di = client if data_index is None else data_index
    seed_cfg = SimpleNamespace(seed=meta["seed"])
    codec = get_codec(meta["compressor"])
    ef = ErrorFeedback(enabled=meta["error_feedback"])
    # per-client RNG stream: free workers fold their id into the run key
    # (independent streams, no cross-thread coordination; the sequential
    # driver replicates the closed-loop global chain instead)
    rng = jax.random.fold_in(jax.random.key(meta["seed"]), client)
    prev_grad = None
    version = int(init.version)   # 0 on a fresh run; the restored
    #                               server version after a resume
    seq = 0
    t0 = time.monotonic()
    total = rounds if rounds is not None else int(meta["rounds"])
    r = 0
    while r < total and not (stop is not None and stop.is_set()):
        rng, urng = jax.random.split(rng)
        sim_t = (pacer.advance(client) if pacer is not None
                 else time.monotonic() - t0)
        newp_s, eff_s = compute.local_round(params, di, urng)
        value = norm = None
        if meta["needs_values"]:
            value = compute.value(newp_s, eff_s, prev_grad)
        if meta["needs_norms"]:
            norm = compute.norm(eff_s)
        reply = None
        if meta["two_phase"]:
            reply = _exchange(channel, UploadMsg(
                kind=wire.REPORT, client=client, seq=seq,
                version=version, sim_time=sim_t, value=value, norm=norm),
                recv_timeout=recv_timeout, stop=stop, retry=retry,
                stats=stats)
            seq += 1
            if reply is None or reply.kind == wire.FINAL:
                break
        if reply is None or reply.kind == wire.DECISION:
            newp = _unstack(newp_s)
            if codec.is_identity:
                payload, enc_seed = newp, 0
            else:
                # free workers seed the encoder from their OWN round
                # counter (the closed loop's global event counter doesn't
                # exist under concurrency); deterministic per client
                enc_seed = _enc_seed(seed_cfg, r, client, _UPLOAD)
                payload, _ = compress_payload(
                    codec, ef, client, _tree_delta(newp, params),
                    seed=enc_seed)
            reply = _exchange(channel, UploadMsg(
                kind=wire.UPDATE, client=client, seq=seq,
                version=version, sim_time=sim_t, codec=codec.name,
                payload=payload, enc_seed=enc_seed),
                recv_timeout=recv_timeout, stop=stop, retry=retry,
                stats=stats)
            seq += 1
        if reply is None or reply.kind == wire.FINAL:
            break
        if reply.kind != wire.DOWNLOAD:
            raise RuntimeError(f"protocol violation: expected download, "
                               f"got {reply.kind!r}")
        params = reply.tree
        version = reply.version
        prev_grad = _unstack(eff_s)
        r += 1
    channel.close()
    return r


class ThreadClientWorker(threading.Thread):
    """One client as a daemon thread over any transport's channel."""

    def __init__(self, compute: ClientCompute, channel, client: int, *,
                 pacer=None, rounds: Optional[int] = None,
                 recv_timeout: float = 30.0, retry=None):
        super().__init__(daemon=True, name=f"serve-client-{client}")
        self.client = client
        self.completed = 0
        self.stats = {"retries": 0}   # reconciled by the chaos soak
        self._kw = dict(pacer=pacer, rounds=rounds,
                        recv_timeout=recv_timeout, retry=retry,
                        stats=self.stats)
        self._compute, self._channel = compute, channel
        # NOT "_stop": threading.Thread owns that name internally
        self._stop_evt = threading.Event()

    def stop(self) -> None:
        self._stop_evt.set()

    def run(self) -> None:
        self.completed = _client_loop(self._compute, self._channel,
                                      self.client, stop=self._stop_evt,
                                      **self._kw)


# ------------------------------------------------- sequential driver ---

class SequentialDriver:
    """The determinism bridge: one thread plays every client in the
    scheduler's completion order and pumps ``server.step()`` between
    sends, so a ``buffer_size=1`` serve run is bit-identical to the
    sequential closed-loop engine (same RNG chain, same encode seeds,
    same scheduler arithmetic — tests/test_algorithms.py asserts it).

    The driver owns the :class:`EventScheduler` (build the server with
    ``sched=driver_sched, account_bytes=False``) and bills each event's
    actual wire bytes into it exactly where the closed loop does."""

    def __init__(self, server, compute: ClientCompute):
        if server._account_bytes:
            raise ValueError(
                "SequentialDriver bills the scheduler itself — build the "
                "FLServer with account_bytes=False and a shared sched")
        self.server = server
        self.compute = compute

    def _pump_recv(self, channel):
        """Alternate server.step() with channel polls until the reply
        lands (single-threaded: the reply is deterministic and queued)."""
        for _ in range(1000):
            msg = channel.recv(timeout=0)
            if msg is not None:
                return msg
            self.server.step(timeout=0)
        raise RuntimeError("serve exchange wedged: no reply after the "
                           "server drained its queue (transport bug?)")

    def run(self) -> "RunResult":
        server, compute = self.server, self.compute
        cfg = server.cfg
        N = cfg.num_clients
        transport = server.transport
        channels = [transport.client_channel(i) for i in range(N)]
        start_ev = server.processed
        if start_ev:
            # a resumed server (restore_checkpoint(fresh_clients=False)):
            # the driver reconstructs every client's live state from the
            # server's checkpointed view — params from the per-client
            # decode base (exactly the tree each client last downloaded),
            # versions and seq watermarks from the server's records —
            # instead of the init broadcast, then replays the global RNG
            # chain up to the checkpoint.  Continuation is bit-equal to
            # the uninterrupted run (tests/test_resilience.py).
            if server.policy.needs_values:
                raise ValueError(
                    "bit-equal bridge resume needs a policy without "
                    "needs_values — per-client prev-grad state lives "
                    "client-side and is not in the server checkpoint")
            codec = get_codec(cfg.compressor)
            if not codec.is_identity and cfg.error_feedback:
                raise ValueError(
                    "bit-equal bridge resume with a codec needs "
                    "error_feedback=False — EF residuals live "
                    "client-side and are not in the server checkpoint")
            meta = {"needs_values": server.policy.needs_values,
                    "needs_norms": server.policy.needs_norms,
                    "two_phase": server.two_phase,
                    "compressor": cfg.compressor,
                    "error_feedback": cfg.error_feedback}
            ef = ErrorFeedback(enabled=cfg.error_feedback)
            params = [server.client_base[i] for i in range(N)]
            versions = [int(v) for v in server.model_version]
            seqs = [int(s) + 1 for s in server._last_seq]
        else:
            server.start()
            inits = [self._pump_recv(ch) for ch in channels]
            meta = inits[0].meta
            params = [init.tree for init in inits]
            codec = get_codec(meta["compressor"])
            ef = ErrorFeedback(enabled=meta["error_feedback"])
            versions = [0] * N
            seqs = [0] * N
        prev_grads = [None] * N
        sched = server.sched
        # the driver owns checkpoint cadence: the server's own save fires
        # inside _finish_event, BEFORE this loop bills the event's bytes
        # into the scheduler — a snapshot taken there is missing the last
        # reschedule and would not resume bit-equal.  Defer every save to
        # after sched.schedule() below.
        ckpt_every, server._ckpt_every = server._ckpt_every, 0
        # the closed loop's exact RNG chain: key(seed) split once for
        # init (the server used the same derivation), then once per event
        rng, _krng = jax.random.split(jax.random.key(cfg.seed))
        for _ in range(start_ev):
            rng, _ = jax.random.split(rng)
        for ev in range(start_ev, server.total_events):
            t_now, i = sched.pop()
            u0, d0 = server.comm.uplink_bytes, server.comm.downlink_bytes
            rng, urng = jax.random.split(rng)
            newp_s, eff_s = compute.local_round(params[i], i, urng)
            value = norm = None
            if meta["needs_values"]:
                value = compute.value(newp_s, eff_s, prev_grads[i])
            if meta["needs_norms"]:
                norm = compute.norm(eff_s)
            ch = channels[i]
            reply = None
            if meta["two_phase"]:
                ch.send(UploadMsg(kind=wire.REPORT, client=i, seq=seqs[i],
                                  version=versions[i], sim_time=t_now,
                                  value=value, norm=norm))
                seqs[i] += 1
                reply = self._pump_recv(ch)
            if reply is None or reply.kind == wire.DECISION:
                newp = _unstack(newp_s)
                if codec.is_identity:
                    payload, enc_seed = newp, 0
                else:
                    # the GLOBAL event counter seeds the encoder — the
                    # bit-exactness hinge vs the closed loop
                    enc_seed = _enc_seed(cfg, ev, i, _UPLOAD)
                    payload, _ = compress_payload(
                        codec, ef, i, _tree_delta(newp, params[i]),
                        seed=enc_seed)
                ch.send(UploadMsg(kind=wire.UPDATE, client=i, seq=seqs[i],
                                  version=versions[i], sim_time=t_now,
                                  codec=codec.name, payload=payload,
                                  enc_seed=enc_seed))
                seqs[i] += 1
                reply = self._pump_recv(ch)
            if reply.kind != wire.DOWNLOAD:
                raise RuntimeError(f"protocol violation: expected "
                                   f"download, got {reply.kind!r}")
            params[i] = reply.tree
            versions[i] = reply.version
            prev_grads[i] = _unstack(eff_s)
            # the round's actual wire bytes reschedule the client — the
            # exact closed-loop call (byte-aware network models included)
            sched.schedule(i, upload_bytes=server.comm.uplink_bytes - u0,
                           download_bytes=server.comm.downlink_bytes - d0)
            if ckpt_every and server.processed % ckpt_every == 0:
                server.save_checkpoint()
        return server.finalize()


# --------------------------------------------------- process workers ---

def _process_client_main(host, port, client, forward_fn, model_cfg, local,
                         images, labels, mask, rounds, pace_seed):
    """Entry point of a spawned client process (module-level so the
    spawn pickler can import it).  Rebuilds the compute bundle from
    numpy inputs; single-phase algorithms only (no eval set here).
    Runs on the CPU backend: the server process holds the accelerator
    (``ProcessClientWorker.start``)."""
    if jax.default_backend() != "cpu":
        raise RuntimeError(f"process client {client} runs on the CPU "
                           f"backend, got {jax.default_backend()!r}")
    from repro.core.client import make_weighted_classifier_loss
    loss_fn = make_weighted_classifier_loss(forward_fn, model_cfg)
    compute = ClientCompute(
        loss_fn=loss_fn, local=local,
        data={"images": images, "labels": labels, "mask": mask},
        num_clients=1)
    pacer = None
    if pace_seed is not None:
        from repro.core.scheduler import SpeedModel
        pacer = ScenarioPacer(SpeedModel.paper_testbed(client + 1,
                                                       pace_seed))
    channel = _SocketChannel(host, port, client)
    _client_loop(compute, channel, client, data_index=0, pacer=pacer,
                 rounds=rounds)


# serializes the environment swap in ProcessClientWorker.start
_SPAWN_ENV_LOCK = threading.Lock()


class ProcessClientWorker:
    """One client as an OS process over the ``socket`` transport.  The
    child rebuilds its jits from picklable pieces (forward fn by module
    reference, model/local dataclasses, its own data rows as numpy) —
    so only registry-style models travel; single-phase algorithms only
    (the Eq. 1 value term needs the server's eval set).

    The child is a simulated edge device and runs on the CPU backend:
    an accelerator belongs to one process, and the server holds it."""

    def __init__(self, address, client: int, *, forward_fn, model_cfg,
                 local, fed_data, rounds: Optional[int] = None,
                 pace_seed: Optional[int] = None):
        import numpy as np
        host, port = address
        sl = slice(client, client + 1)
        self._proc = multiprocessing.get_context("spawn").Process(
            target=_process_client_main,
            args=(host, port, client, forward_fn, model_cfg, local,
                  np.asarray(fed_data.images[sl]),
                  np.asarray(fed_data.labels[sl]),
                  np.asarray(fed_data.mask[sl]), rounds, pace_seed),
            daemon=True, name=f"serve-client-{client}")
        self.client = client

    def start(self) -> None:
        """Spawn the child with ``JAX_PLATFORMS=cpu`` in its environment.
        A spawned child inherits the parent's environment when it is
        exec'd, and it imports jax (unpickling its target and args)
        before any code of ours runs there — so the variable is set in
        the parent for the duration of the spawn."""
        with _SPAWN_ENV_LOCK:
            old = os.environ.get("JAX_PLATFORMS")
            os.environ["JAX_PLATFORMS"] = "cpu"
            try:
                self._proc.start()
            finally:
                if old is None:
                    del os.environ["JAX_PLATFORMS"]
                else:
                    os.environ["JAX_PLATFORMS"] = old

    def join(self, timeout: Optional[float] = None) -> None:
        self._proc.join(timeout)

    def kill(self) -> None:
        """Hard-kill the worker (the killed-client transport test)."""
        self._proc.kill()

    @property
    def exitcode(self):
        return self._proc.exitcode
