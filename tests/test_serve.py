"""repro.serve — the live-service layer (docs/SERVING.md).

The transport contract (per-client FIFO / no drops under concurrent
producers, bounded-queue backpressure, non-blocking server receives),
the server lifecycle (graceful drain commits every buffered update, a
wedged two-phase exchange is discarded through the failure hook, a
killed client worker trips the stall timeout instead of wedging the
loop), the registry semantics, and the end-to-end acceptance runs:
live threaded federations — inproc and socket — whose obs counters
reconcile exactly against ``CommStats``, plus multi-tenant interleaving.

The determinism bridge (sequential serve == closed-loop engine, bit for
bit) lives with the other golden-parity tests in test_algorithms.py.
"""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FLRunConfig, Federation
from repro.core.client import (LocalSpec, make_evaluator,
                               make_weighted_classifier_loss)
from repro.data.partition import iid_partition
from repro.data.synthetic import synthetic_mnist
from repro.models.cnn import MLPConfig, mlp_forward, mlp_init
from repro.obs import ObsConfig
from repro.serve import (FLServer, InprocTransport, MultiTenantServer,
                         SequentialDriver, available_transports,
                         get_transport, launch_serving, register_transport,
                         serve_run)
from repro.serve import messages as wire
from repro.serve.messages import BroadcastMsg, UploadMsg, msg_from_wire
from repro.serve.socket_transport import SocketTransport


@pytest.fixture(scope="module")
def setup():
    xtr, ytr, xte, yte = synthetic_mnist(4 * 100 + 200, 200, seed=0)
    mcfg = MLPConfig(hidden=(16,))
    loss_fn = make_weighted_classifier_loss(mlp_forward, mcfg)
    evaluate = make_evaluator(mlp_forward, mcfg, xte, yte, batch=200)
    fed = iid_partition(xtr, ytr, 4, samples_per_client=100, seed=0)
    return mcfg, loss_fn, evaluate, fed


def _cfg(alg="afl", **kw):
    base = dict(algorithm=alg, num_clients=4, rounds=2,
                local=LocalSpec(batch_size=32, local_rounds=1, lr=0.1),
                target_acc=0.99, events_per_eval=4, seed=7)
    base.update(kw)
    return FLRunConfig(**base)


def _callables(setup):
    mcfg, loss_fn, evaluate, fed = setup
    return dict(init_params_fn=lambda k: mlp_init(mcfg, k),
                loss_fn=loss_fn, fed_data=fed, evaluate_fn=evaluate)


def _upload(client, seq, tree, sim_time=1.0):
    return UploadMsg(kind=wire.UPDATE, client=client, seq=seq, version=0,
                     sim_time=sim_time, payload=tree)


# ------------------------------------------------------------- registry ---

class TestTransportRegistry:
    def test_builtins_first_in_stable_order(self):
        names = available_transports()
        assert names[:2] == ("inproc", "socket")

    def test_unknown_name_fails_loudly(self):
        with pytest.raises(ValueError, match="inproc"):
            get_transport("carrier-pigeon")

    def test_register_resolve_duplicate_overwrite(self):
        from repro.serve import transport as reg

        def factory(num_clients, capacity=0):
            return InprocTransport(num_clients, capacity)

        register_transport("x-test", factory)
        try:
            assert get_transport("x-test") is factory
            assert "x-test" in available_transports()
            with pytest.raises(ValueError, match="already registered"):
                register_transport("x-test", factory)
            register_transport("x-test", factory, overwrite=True)
        finally:
            del reg._REGISTRY["x-test"]

    def test_serve_accepts_transport_instance(self, setup):
        """A ready Transport object passes through ``serve_run``
        untouched (the caller owns its lifecycle)."""
        tr = InprocTransport(4)
        res = serve_run(_cfg("afl", rounds=1), transport=tr,
                        driver="sequential", **_callables(setup))
        assert res.comm.model_uploads == 4
        tr.close()

    def test_unknown_driver_fails_loudly(self, setup):
        with pytest.raises(ValueError, match="sequential"):
            serve_run(_cfg(), driver="carrier-pigeon", **_callables(setup))


# --------------------------------------------------- transport semantics ---

class TestTransportSemantics:
    def test_concurrent_producers_fifo_no_drops(self):
        """The load-bearing transport invariant: any interleaving across
        clients, but one client's stream arrives complete and in order
        (the two-phase exchange and staleness accounting depend on it)."""
        N, per = 4, 30
        tr = InprocTransport(N)
        chans = [tr.client_channel(i) for i in range(N)]

        def produce(i):
            for s in range(per):
                assert chans[i].send(_upload(i, s, {"x": s}), timeout=1.0)

        threads = [threading.Thread(target=produce, args=(i,))
                   for i in range(N)]
        for t in threads:
            t.start()
        seen = {i: [] for i in range(N)}
        got = 0
        deadline = time.monotonic() + 10
        while got < N * per and time.monotonic() < deadline:
            for msg in tr.drain_uploads(16, timeout=0.5):
                seen[msg.client].append(msg.seq)
                got += 1
        for t in threads:
            t.join()
        assert got == N * per
        for i in range(N):
            assert seen[i] == list(range(per)), f"client {i} lost order"

    def test_backpressure_bounds_queue_depth(self):
        """The upload queue is bounded: a full queue blocks the sender up
        to its timeout and returns False instead of dropping."""
        tr = InprocTransport(1, capacity=3)
        ch = tr.client_channel(0)
        for s in range(3):
            assert ch.send(_upload(0, s, None), timeout=0.2)
        t0 = time.monotonic()
        assert ch.send(_upload(0, 3, None), timeout=0.1) is False
        assert time.monotonic() - t0 >= 0.1     # blocked, then refused
        assert tr.queue_depth() == 3
        assert tr.recv_upload(timeout=0.1).seq == 0
        assert ch.send(_upload(0, 3, None), timeout=0.2)

    def test_drain_waits_only_for_first_and_caps_window(self):
        tr = InprocTransport(1)
        ch = tr.client_channel(0)
        for s in range(10):
            ch.send(_upload(0, s, None))
        win = tr.drain_uploads(4, timeout=0.5)
        assert [m.seq for m in win] == [0, 1, 2, 3]
        assert tr.queue_depth() == 6
        tr.close()
        t0 = time.monotonic()
        assert InprocTransport(1).drain_uploads(4, timeout=0.15) == []
        assert time.monotonic() - t0 >= 0.15

    def test_server_dedups_replayed_seq(self, setup):
        """A replayed seq (an at-least-once retry or a chaos duplicate)
        is absorbed: processed once, counted as a duplicate, and the
        cached reply is re-sent with the matching ack_seq."""
        cb = _callables(setup)
        tr = InprocTransport(4)
        server = FLServer(_cfg("afl"), init_params_fn=cb["init_params_fn"],
                          evaluate_fn=cb["evaluate_fn"], transport=tr)
        tree = server.global_params
        ch = tr.client_channel(0)
        ch.send(_upload(0, 5, tree))
        server.step(timeout=0.2)
        assert server.processed == 1
        first = ch.recv(timeout=1.0)
        assert first.kind == wire.DOWNLOAD and first.ack_seq == 5
        ch.send(_upload(0, 5, tree))   # replayed seq
        server.step(timeout=0.2)
        assert server.processed == 1          # NOT re-processed
        assert server.duplicates == 1
        replay = ch.recv(timeout=1.0)          # cached reply re-sent
        assert replay.kind == wire.DOWNLOAD and replay.ack_seq == 5
        tr.close()

    def test_socket_round_trip_preserves_bits(self):
        """Localhost TCP frames: upload in, broadcast back, float bits
        identical after the numpy hop; FIFO by TCP byte order."""
        tr = SocketTransport(1)
        ch = tr.client_channel(0)
        payload = {"w": np.linspace(-1, 1, 7, dtype=np.float32),
                   "b": np.float32(0.25)}
        ch.send(UploadMsg(kind=wire.REPORT, client=0, seq=0, version=0,
                          value=3.5))
        ch.send(_upload(0, 1, payload))
        first = tr.recv_upload(timeout=5.0)
        second = tr.recv_upload(timeout=5.0)
        assert (first.kind, first.seq, first.value) == (wire.REPORT, 0, 3.5)
        assert second.seq == 1 and second.recv_host > 0
        np.testing.assert_array_equal(second.payload["w"], payload["w"])
        bcast_tree = {"w": jnp.arange(3, dtype=jnp.float32) / 3.0}
        tr.send_broadcast(0, BroadcastMsg(kind=wire.DOWNLOAD, version=9,
                                          tree=bcast_tree))
        reply = ch.recv(timeout=5.0)
        assert reply.kind == wire.DOWNLOAD and reply.version == 9
        np.testing.assert_array_equal(reply.tree["w"],
                                      np.asarray(bcast_tree["w"]))
        ch.close()
        tr.close()

    def test_wire_schema_mismatch_is_loud(self):
        import pickle
        body = pickle.dumps(("serve-wire/v0", None))
        with pytest.raises(ValueError, match="schema mismatch"):
            msg_from_wire(body)


# ----------------------------------------------------- server lifecycle ---

class TestServerLifecycle:
    def test_graceful_drain_commits_partial_buffer(self, setup):
        """finalize() never loses an accepted update: three buffered
        reconstructions under K=4 commit as one partial flush."""
        cb = _callables(setup)
        cfg = _cfg("afl", num_clients=3, rounds=1, buffer_size=4,
                   events_per_eval=3)
        tr = InprocTransport(3)
        server = FLServer(cfg, init_params_fn=cb["init_params_fn"],
                          evaluate_fn=cb["evaluate_fn"], transport=tr)
        server.start()
        init = server.global_params
        for i in range(3):
            shifted = jax.tree.map(lambda x, _i=i: x + 0.01 * (_i + 1),
                                   init)
            tr.client_channel(i).send(_upload(i, 0, shifted))
        deadline = time.monotonic() + 20
        while server.processed < 3 and time.monotonic() < deadline:
            server.step(timeout=0.5)
        assert server.processed == 3
        assert len(server._buffer) == 3 and server.server_version == 0
        res = server.finalize()
        assert server.server_version == 1 and not server._buffer
        moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                             server.global_params, init)
        assert max(jax.tree.leaves(moved)) > 0
        assert res.comm.model_uploads == 3
        tr.close()

    def test_wedged_two_phase_exchange_discarded_via_failure_hook(
            self, setup):
        """A client accepted for upload that never delivers its payload
        (killed worker) is discarded at drain time through
        ``obs.failure`` — the server finishes cleanly regardless."""
        cb = _callables(setup)
        cfg = _cfg("vafl", obs=ObsConfig())
        tr = InprocTransport(4)
        server = FLServer(cfg, init_params_fn=cb["init_params_fn"],
                          evaluate_fn=cb["evaluate_fn"], transport=tr)
        server.start()
        tr.client_channel(0).send(UploadMsg(
            kind=wire.REPORT, client=0, seq=0, version=0, sim_time=1.0,
            value=1e9))
        server.step(timeout=0.5)
        assert 0 in server._pending          # accepted, payload never lands
        res = server.finalize(drain_timeout=0.1)
        assert not server._pending
        assert res.metrics["counters"].get("failures", 0) == 1
        tr.close()

    def test_stalled_fleet_trips_timeout_not_wedge(self, setup):
        cb = _callables(setup)
        tr = InprocTransport(4)
        server = FLServer(_cfg("afl"), init_params_fn=cb["init_params_fn"],
                          evaluate_fn=cb["evaluate_fn"], transport=tr)
        server.start()
        t0 = time.monotonic()
        res = server.run(stall_timeout=0.3)       # nobody ever uploads
        assert time.monotonic() - t0 < 5.0
        assert res.comm.model_uploads == 0
        tr.close()

    def test_sequential_driver_demands_shared_ledger(self, setup):
        """The bridge driver bills the scheduler itself — a server still
        accounting its own bytes would double-bill, so it's refused."""
        cb = _callables(setup)
        tr = InprocTransport(4)
        server = FLServer(_cfg("afl"), init_params_fn=cb["init_params_fn"],
                          evaluate_fn=cb["evaluate_fn"], transport=tr)
        with pytest.raises(ValueError, match="account_bytes"):
            SequentialDriver(server, compute=None)
        tr.close()

    def test_killed_process_worker_does_not_wedge_server(self, setup):
        """The hard case: a client OS process SIGKILLed mid-run.  The
        server keeps draining what arrived, trips the stall timeout and
        finalizes — it never blocks on the dead client."""
        from repro.serve import ProcessClientWorker
        mcfg, loss_fn, evaluate, fed = setup
        cfg = _cfg("afl", num_clients=4, rounds=10_000,
                   events_per_eval=100_000)
        tr = SocketTransport(4)
        server = FLServer(cfg, init_params_fn=lambda k: mlp_init(mcfg, k),
                          evaluate_fn=evaluate, transport=tr)
        worker = ProcessClientWorker(
            tr.address, 0, forward_fn=mlp_forward, model_cfg=mcfg,
            local=cfg.local, fed_data=fed)
        server.start()
        worker.start()
        # pump manually until the first event lands (the child process
        # pays a cold jax import, far longer than any sane stall), THEN
        # kill it and let the hot loop prove it trips the stall timeout
        deadline = time.monotonic() + 120
        while server.processed < 1 and time.monotonic() < deadline:
            server.step(timeout=0.1)
        assert server.processed >= 1, "worker never delivered an upload"
        worker.kill()
        res = server.run(stall_timeout=1.5)
        worker.join(timeout=10)
        assert worker.exitcode is not None      # actually dead
        assert 1 <= server.processed < server.total_events
        assert res.comm.model_uploads == server.processed
        tr.close()

    @pytest.mark.parametrize("parent_platforms", [None, "tpu"])
    def test_process_worker_child_runs_on_cpu(self, setup, tmp_path,
                                              monkeypatch, parent_platforms):
        """The server process holds the accelerator, so a spawned client
        must come up with JAX_PLATFORMS=cpu whatever the parent's
        environment says — and the parent's environment is left as it
        was.  The worker's child target is swapped for one that records
        the environment it was started with."""
        from repro.serve import ProcessClientWorker
        mcfg, loss_fn, evaluate, fed = setup
        if parent_platforms is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", parent_platforms)
        out = tmp_path / "child_env.txt"
        worker = ProcessClientWorker(
            ("127.0.0.1", 0), 0, forward_fn=mlp_forward, model_cfg=mcfg,
            local=_cfg().local, fed_data=fed)
        worker._proc._target = _record_child_platforms
        worker._proc._args = (str(out),)
        worker.start()
        assert os.environ.get("JAX_PLATFORMS") == parent_platforms
        worker.join(timeout=120)
        assert worker.exitcode == 0
        assert out.read_text() == "cpu"


def _record_child_platforms(path):
    """Spawn target of the process-worker test (module level so the
    spawned child can import it)."""
    with open(path, "w") as f:
        f.write(os.environ.get("JAX_PLATFORMS", "<unset>"))


# ------------------------------------------------------ live federations ---

def _reconciled(res):
    c = res.metrics["counters"]
    return (c.get("uploads", 0) == res.comm.model_uploads
            and c.get("scalar_reports", 0) == res.comm.scalar_reports
            and c.get("broadcasts", 0) == res.comm.broadcasts
            and c.get("upload_payload_bytes", 0)
            == res.comm.upload_payload_bytes)


class TestLiveServe:
    def test_live_vafl_compressed_reconciles(self, setup):
        """The acceptance run: >=2 genuinely concurrent thread workers,
        vafl + topk0.1_int8, two-phase protocol over inproc — completes
        end-to-end and the obs trace reconciles against CommStats."""
        mcfg, loss_fn, evaluate, fed = setup
        federation = Federation(
            data=fed, algorithm="vafl", compressor="topk0.1_int8",
            obs=ObsConfig(), init_params_fn=lambda k: mlp_init(mcfg, k),
            loss_fn=loss_fn, evaluate_fn=evaluate,
            local=LocalSpec(batch_size=32, local_rounds=1, lr=0.1),
            seed=7)
        res = federation.serve(rounds=2)
        assert res.comm.broadcasts == 2 * 4     # every event completed
        assert res.comm.scalar_reports == 2 * 4
        assert 0 < res.comm.model_uploads <= 2 * 4
        assert res.comm.upload_payload_bytes > 0
        assert res.records and np.isfinite(res.records[-1].global_acc)
        assert _reconciled(res)
        assert res.metrics["counters"].get("failures", 0) == 0
        assert res.metrics["histograms"]["queue_depth"]["count"] > 0

    def test_live_capacity_bounds_observed_depth(self, setup):
        """A bounded transport keeps the observed queue depth within
        capacity + one drained window even under free-running workers."""
        cfg = _cfg("afl", rounds=2, obs=ObsConfig())
        res = serve_run(cfg, capacity=2, **_callables(setup))
        assert res.comm.broadcasts == 2 * 4
        qd = res.metrics["histograms"]["queue_depth"]
        assert qd["max"] <= 2 + 4
        assert _reconciled(res)

    def test_live_socket_transport(self, setup):
        """The socket transport end-to-end: thread workers over real
        localhost TCP connections, bits surviving the numpy hop."""
        cfg = _cfg("afl", rounds=1)
        res = serve_run(cfg, transport="socket", stall_timeout=20,
                        **_callables(setup))
        assert res.comm.broadcasts == 4
        assert res.comm.model_uploads == 4

    def test_scenario_paced_workers(self, setup):
        """``pace=True``: workers draw service times from the run's
        scenario fleet, so upload sim_times are simulated seconds."""
        cfg = _cfg("afl", rounds=1, scenario="paper_testbed")
        res = serve_run(cfg, pace=True, **_callables(setup))
        assert res.comm.broadcasts == 4
        assert res.records[-1].time > 0

    def test_multi_tenant_two_federations_one_mesh(self, setup):
        """Two independent federations (different algorithms and codecs)
        interleave through one round-robin loop on one device; each keeps
        its own transport, CommStats and result."""
        cb = _callables(setup)
        cfg_a = _cfg("afl", rounds=2)
        cfg_b = _cfg("vafl", rounds=2, compressor="topk0.1_int8")
        sa, wa, ta = launch_serving(cfg_a, **cb)
        sb, wb, tb = launch_serving(cfg_b, **cb)
        mt = MultiTenantServer([sa, sb])
        mt.start()
        for w in wa + wb:
            w.start()
        try:
            res_a, res_b = mt.run(stall_timeout=30)
        finally:
            for w in wa + wb:
                w.stop()
            for w in wa + wb:
                w.join(timeout=5)
            ta.close()
            tb.close()
        assert res_a.comm.broadcasts == 2 * 4
        assert res_b.comm.broadcasts == 2 * 4
        assert res_a.comm.model_uploads == 2 * 4      # afl always ships
        assert res_b.comm.scalar_reports == 2 * 4     # vafl reports first
        assert res_b.comm.upload_payload_bytes < res_a.comm.model_bytes * 8
