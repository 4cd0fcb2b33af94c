"""Batched async execution engine (docs/ASYNC_ENGINE.md).

Covers the engine's contract: the window=1/buffer=1 configuration must
reproduce the sequential per-event runtime EXACTLY (upload decisions,
CommStats, records) for identity and compressed codecs; plus the hot-path
crash regressions this PR fixes (small shards, small/ragged test sets,
scheduler busy-time accounting, sync-barrier participation).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FLRunConfig, run_event_driven, run_round_based
from repro.core.aggregation import async_mix, buffered_mix
from repro.core.client import (LocalSpec, make_evaluator, make_local_update,
                               make_weighted_classifier_loss)
from repro.core.metrics import RunResult
from repro.core.scheduler import EventScheduler, SpeedModel
from repro.data.partition import iid_partition
from repro.data.synthetic import synthetic_mnist
from repro.models.cnn import MLPConfig, mlp_forward, mlp_init


@pytest.fixture(scope="module")
def setup():
    xtr, ytr, xte, yte = synthetic_mnist(7 * 300 + 1000, 1000, seed=0)
    mcfg = MLPConfig(hidden=(64,))
    loss_fn = make_weighted_classifier_loss(mlp_forward, mcfg)
    evaluate = make_evaluator(mlp_forward, mcfg, xte, yte, batch=500)
    fed = iid_partition(xtr, ytr, 7, samples_per_client=300, seed=0)
    return xtr, ytr, xte, yte, mcfg, loss_fn, evaluate, fed


def _run(setup, alg, engine, rounds=4, comp="identity", **kw):
    _, _, _, _, mcfg, loss_fn, evaluate, fed = setup
    rc = FLRunConfig(algorithm=alg, num_clients=7, rounds=rounds,
                     local=LocalSpec(batch_size=32, local_rounds=1, lr=0.1),
                     target_acc=0.90,
                     events_per_eval=kw.pop("events_per_eval", 7),
                     compressor=comp, engine=engine, **kw)
    return run_event_driven(rc, init_params_fn=lambda k: mlp_init(mcfg, k),
                            loss_fn=loss_fn, fed_data=fed,
                            evaluate_fn=evaluate)


# ------------------------------------------------------- scheduler window ---

class TestPopWindow:
    def test_window_of_one_is_pop(self):
        a = EventScheduler(5, SpeedModel.paper_testbed(5, seed=3))
        b = EventScheduler(5, SpeedModel.paper_testbed(5, seed=3))
        for _ in range(5):
            t, c = a.pop()
            tw, cw = b.pop_window(1)
            assert (t, c) == (float(tw[0]), int(cw[0]))
            assert a.now == b.now

    def test_window_pops_earliest_in_order(self):
        a = EventScheduler(6, SpeedModel.paper_testbed(6, seed=1))
        b = EventScheduler(6, SpeedModel.paper_testbed(6, seed=1))
        ref = [a.pop() for _ in range(4)]
        times, clients = b.pop_window(4)
        assert [c for _, c in ref] == list(clients)
        assert [t for t, _ in ref] == list(times)
        assert times[-1] == ref[-1][0] == b.now
        # no client appears twice before being rescheduled
        assert len(set(clients)) == len(clients)

    def test_window_clamped_to_heap(self):
        s = EventScheduler(3, SpeedModel.paper_testbed(3, seed=0))
        _, clients = s.pop_window(10)
        assert len(clients) == 3

    def test_schedule_from_own_completion_time(self):
        """Rescheduling with start=<own completion> must not wait for the
        window's last event (no simulated-clock barrier): the fast client
        of the paper testbed restarts before the slow Pis even finish."""
        s = EventScheduler(4, SpeedModel.paper_testbed(4, seed=9))
        times, clients = s.pop_window(4)
        fast = int(clients[0])              # earliest finisher (laptop)
        s.schedule(fast, start=float(times[0]))
        nxt = min(e.time for e in s.heap if e.client == fast)
        assert times[0] < nxt < s.now

    def test_extra_delay_not_counted_busy(self):
        """Network latency delays the next completion but is idle time, not
        service time (regression: it used to inflate client_busy_time)."""
        a = EventScheduler(3, SpeedModel.paper_testbed(3, seed=5))
        b = EventScheduler(3, SpeedModel.paper_testbed(3, seed=5))
        a.schedule(0, extra_delay=0.0)
        b.schedule(0, extra_delay=5.0)
        np.testing.assert_allclose(a.client_busy_time, b.client_busy_time)
        assert b.busy_until[0] == pytest.approx(a.busy_until[0] + 5.0)

    def test_idle_fraction_grows_with_delay(self):
        slow = EventScheduler(2, SpeedModel.paper_testbed(2, seed=2))
        fast = EventScheduler(2, SpeedModel.paper_testbed(2, seed=2))
        for _ in range(8):
            _, c = slow.pop()
            slow.schedule(c, extra_delay=2.0)
            _, c = fast.pop()
            fast.schedule(c)
        assert slow.idle_fraction().mean() > fast.idle_fraction().mean()


# ------------------------------------------------- hot-path crash fixes ---

class TestSmallShardLocalUpdate:
    def test_shard_smaller_than_batch_trains(self, setup):
        """Regression: M=8 < B=32 crashed with a reshape error; now the
        effective batch clamps to the shard size."""
        xtr, ytr, _, _, mcfg, loss_fn, _, _ = setup
        fed = iid_partition(xtr, ytr, 3, samples_per_client=8, seed=0)
        upd = make_local_update(loss_fn, LocalSpec(batch_size=32, lr=0.1))
        data = {"images": jnp.asarray(fed.images),
                "labels": jnp.asarray(fed.labels),
                "mask": jnp.asarray(fed.mask)}
        params = mlp_init(mcfg, jax.random.key(0))
        stacked = jax.tree.map(lambda x: jnp.broadcast_to(x, (3,) + x.shape),
                               params)
        newp, eff, loss = upd(stacked, data, jax.random.key(1))
        assert np.isfinite(float(loss.mean() if loss.ndim else loss))
        moved = float(jax.vmap(
            lambda a, b: sum(jnp.sum(jnp.abs(x - y)) for x, y in
                             zip(jax.tree.leaves(a), jax.tree.leaves(b)))
        )(newp, stacked).sum())
        assert moved > 0.0


class TestEvaluatorTail:
    def _manual_acc(self, mcfg, params, xte, yte):
        logits = mlp_forward(mcfg, params, jnp.asarray(xte))
        return float(np.mean(np.argmax(np.asarray(logits), -1)
                             == np.asarray(yte)))

    def test_test_set_smaller_than_batch(self, setup):
        """Regression: 900 samples at batch=1000 crashed / divided by zero."""
        _, _, xte, yte, mcfg, _, _, _ = setup
        params = mlp_init(mcfg, jax.random.key(0))
        ev = make_evaluator(mlp_forward, mcfg, xte[:900], yte[:900],
                            batch=1000)
        acc = float(ev(params))
        assert acc == pytest.approx(
            self._manual_acc(mcfg, params, xte[:900], yte[:900]), abs=1e-6)

    def test_tail_remainder_counted(self, setup):
        """Regression: len % batch used to be silently dropped, biasing the
        reported accuracy."""
        _, _, xte, yte, mcfg, _, _, _ = setup
        params = mlp_init(mcfg, jax.random.key(0))
        ev = make_evaluator(mlp_forward, mcfg, xte[:250], yte[:250],
                            batch=100)
        acc = float(ev(params))
        assert acc == pytest.approx(
            self._manual_acc(mcfg, params, xte[:250], yte[:250]), abs=1e-6)

    def test_exact_division_unchanged(self, setup):
        _, _, xte, yte, mcfg, _, _, _ = setup
        params = mlp_init(mcfg, jax.random.key(0))
        ev = make_evaluator(mlp_forward, mcfg, xte, yte, batch=500)
        acc = float(ev(params))
        assert acc == pytest.approx(
            self._manual_acc(mcfg, params, xte, yte), abs=1e-6)


# ------------------------------------------------------------ equivalence ---

class TestEngineEquivalence:
    """The acceptance contract: pop_window(max_batch=1) + buffer_size=1 must
    reproduce the sequential runtime's upload decisions and CommStats
    exactly on the N=7 paper testbed, for identity and topk0.1_int8."""

    @pytest.mark.parametrize("alg", ["afl", "vafl", "eaflm"])
    @pytest.mark.parametrize("comp", ["identity", "topk0.1_int8"])
    def test_window1_buffer1_bitmatches_sequential(self, setup, alg, comp):
        seq = _run(setup, alg, "sequential", comp=comp)
        bat = _run(setup, alg, "batched", comp=comp, max_batch=1,
                   buffer_size=1)
        assert dataclasses.asdict(seq.comm) == dataclasses.asdict(bat.comm)
        assert [(r.round, r.time, r.global_acc, r.uploads_so_far)
                for r in seq.records] == \
               [(r.round, r.time, r.global_acc, r.uploads_so_far)
                for r in bat.records]
        assert seq.idle_fraction == bat.idle_fraction

    @pytest.mark.parametrize("alg", ["afl", "fedavg"])
    def test_unknown_engine_rejected(self, setup, alg):
        with pytest.raises(ValueError):
            _run(setup, alg, "warp-drive")

    @pytest.mark.parametrize("comp", ["identity", "topk0.1_int8"])
    def test_sharded_single_device_bitmatches_sequential(self, setup, comp):
        """shard_clients on a 1-device mesh must change NOTHING: the
        sharding constraint is a no-op there, so the w=1/K=1 contract
        holds bit-for-bit through the sharded jit set too."""
        seq = _run(setup, "vafl", "sequential", comp=comp)
        sh = _run(setup, "vafl", "batched", comp=comp, max_batch=1,
                  buffer_size=1, shard_clients=True)
        assert dataclasses.asdict(seq.comm) == dataclasses.asdict(sh.comm)
        assert [(r.round, r.time, r.global_acc, r.uploads_so_far)
                for r in seq.records] == \
               [(r.round, r.time, r.global_acc, r.uploads_so_far)
                for r in sh.records]

    @pytest.mark.parametrize("alg", ["afl", "vafl"])
    def test_sharded_full_window_bitmatches_unsharded(self, setup, alg):
        """The full-window fast path under shard_clients (1-device mesh)
        vs the plain batched engine: identical records and comm."""
        ref = _run(setup, alg, "batched", buffer_size=2)
        sh = _run(setup, alg, "batched", buffer_size=2, shard_clients=True)
        assert dataclasses.asdict(ref.comm) == dataclasses.asdict(sh.comm)
        assert [r.global_acc for r in ref.records] == \
               [r.global_acc for r in sh.records]

    def test_tree_shard_roundtrip(self):
        """tree_shard places a stacked tree on the client sharding and
        tree_gather_sharded reassembles it to host numpy unchanged."""
        from repro.common.pytree import tree_gather_sharded, tree_shard
        from repro.distributed.sharding import client_state_sharding
        n = 2 * jax.device_count()       # always divides the device count
        tree = {"w": jnp.arange(n * 6.0).reshape(n, 3, 2),
                "b": jnp.ones((n, 5), jnp.float32)}
        sharding = client_state_sharding(n)
        assert sharding is not None
        placed = tree_shard(tree, sharding)
        back = tree_gather_sharded(placed)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert isinstance(b, np.ndarray)
            np.testing.assert_array_equal(np.asarray(a), b)
        assert tree_shard(tree, None) is tree   # unsharded fallback

    def test_multi_device_sharded_parity(self, setup):
        """The real thing: 4 forced CPU devices, stacked client state
        sharded on the ("clients",) mesh — upload decisions identical to
        the sequential runtime and record accuracies equal to fp32 noise
        (per-client lanes are independent, so in practice they match
        exactly; the tolerance only guards against cross-device layout
        differences)."""
        import subprocess
        import sys
        import textwrap
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import sys; sys.path.insert(0, "src")
            import numpy as np, jax
            from repro.core import FLRunConfig, run_event_driven
            from repro.core.client import (LocalSpec, make_evaluator,
                                           make_weighted_classifier_loss)
            from repro.data.partition import iid_partition
            from repro.data.synthetic import synthetic_mnist
            from repro.models.cnn import MLPConfig, mlp_forward, mlp_init

            assert jax.device_count() == 4
            xtr, ytr, xte, yte = synthetic_mnist(8 * 60 + 200, 200, seed=0)
            mcfg = MLPConfig(hidden=(16,))
            loss_fn = make_weighted_classifier_loss(mlp_forward, mcfg)
            evaluate = make_evaluator(mlp_forward, mcfg, xte, yte, batch=200)
            fed = iid_partition(xtr, ytr, 8, samples_per_client=60, seed=0)

            def go(**kw):
                rc = FLRunConfig(algorithm="vafl", num_clients=8, rounds=2,
                                 local=LocalSpec(batch_size=32,
                                                 local_rounds=1, lr=0.1),
                                 target_acc=0.99, events_per_eval=8, **kw)
                return run_event_driven(
                    rc, init_params_fn=lambda k: mlp_init(mcfg, k),
                    loss_fn=loss_fn, fed_data=fed, evaluate_fn=evaluate)

            seq = go()
            sh = go(engine="batched", max_batch=1, buffer_size=1,
                    shard_clients=True)
            assert seq.comm.model_uploads == sh.comm.model_uploads
            np.testing.assert_allclose(
                [r.global_acc for r in seq.records],
                [r.global_acc for r in sh.records], rtol=0, atol=1e-6)
            full = go(engine="batched", buffer_size=4, shard_clients=True)
            ref = go(engine="batched", buffer_size=4)
            assert full.comm.model_uploads == ref.comm.model_uploads
            np.testing.assert_allclose(
                [r.global_acc for r in full.records],
                [r.global_acc for r in ref.records], rtol=0, atol=1e-6)
            print("OK")
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, cwd=".")
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout

    def test_multi_device_indivisible_clients_raise(self):
        """shard_clients=True with a client count the device count does
        not divide is refused (4 forced CPU devices, N=6) — by the
        sharding helper and by a run — instead of going on replicated."""
        import subprocess
        import sys
        import textwrap
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import sys; sys.path.insert(0, "src")
            import jax
            from repro.core import FLRunConfig, run_event_driven
            from repro.core.client import LocalSpec, make_weighted_classifier_loss
            from repro.data.partition import iid_partition
            from repro.data.synthetic import synthetic_mnist
            from repro.distributed.sharding import client_state_sharding
            from repro.models.cnn import MLPConfig, mlp_forward, mlp_init

            assert jax.device_count() == 4
            assert client_state_sharding(8) is not None
            try:
                client_state_sharding(6)
            except ValueError as e:
                assert "6 clients" in str(e), e
            else:
                raise SystemExit("client_state_sharding(6) did not raise")

            xtr, ytr, xte, yte = synthetic_mnist(6 * 40, 40, seed=0)
            mcfg = MLPConfig(hidden=(8,))
            fed = iid_partition(xtr, ytr, 6, samples_per_client=40, seed=0)
            rc = FLRunConfig(algorithm="afl", num_clients=6, rounds=1,
                             local=LocalSpec(batch_size=20, local_rounds=1,
                                             lr=0.1),
                             engine="batched", shard_clients=True)
            try:
                run_event_driven(
                    rc, init_params_fn=lambda k: mlp_init(mcfg, k),
                    loss_fn=make_weighted_classifier_loss(mlp_forward, mcfg),
                    fed_data=fed, evaluate_fn=lambda p: 0.0)
            except ValueError as e:
                assert "do not divide over 4 devices" in str(e), e
            else:
                raise SystemExit("shard_clients run with N=6 did not raise")
            print("OK")
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, cwd=".")
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout


    @pytest.mark.parametrize("backend", ["value_backend",
                                         "tree_grad_diff_sq_norm"])
    def test_shard_clients_refuses_pallas_value_backend(self, backend):
        """The compiler cannot partition the grad_diff_norm kernel inside
        the vmapped value term over sharded client state, so the config
        refuses the pair loudly; each alone is accepted."""
        from repro.kernels.grad_diff_norm import ops as gd_ops
        fn = getattr(gd_ops, backend)
        with pytest.raises(ValueError, match="grad_diff_norm"):
            FLRunConfig(algorithm="vafl", engine="batched",
                        shard_clients=True, value_backend=fn)
        FLRunConfig(algorithm="vafl", engine="batched", value_backend=fn)
        FLRunConfig(algorithm="vafl", engine="batched", shard_clients=True,
                    value_backend=lambda a, b: 0.0)

# --------------------------------------------------- device codec path ---

class TestDeviceCodecPath:
    """The topk_int8 codec's compiled round trip: the batched engine
    decides a window's uploads without reading the device, then reads
    every upload's kept count in one copy, and counts the same bytes as
    the Payload path."""

    @pytest.mark.parametrize("buffer_size", [1, 3])
    def test_decision_loop_reads_nothing_back(self, setup, monkeypatch,
                                              buffer_size):
        import contextlib

        import repro.core.runtimes.batched as batched
        from jax._src.array import ArrayImpl
        from repro.compress import TopKQuantCodec
        from repro.obs import Observer

        kw = dict(comp="topk0.1_int8", buffer_size=buffer_size,
                  max_batch=4, obs=True)
        # the same run forced onto the Payload path
        monkeypatch.setattr(TopKQuantCodec, "device_roundtrip", None)
        plain = _run(setup, "vafl", "batched", **kw)
        monkeypatch.undo()

        # a device value read on the host inside "window.decide" counts as
        # a read, except in the one read-back of the wires' bytes.  The
        # transfer guard catches a copy where device memory is not host
        # memory; on the CPU, the count of reads does
        state = {"guard": False, "reads": 0, "readbacks": 0}
        value = ArrayImpl._value

        def counted(arr):
            state["reads"] += state["guard"]
            return value.fget(arr)
        monkeypatch.setattr(ArrayImpl, "_value", property(counted))

        span = Observer.span

        @contextlib.contextmanager
        def guarded_span(obs, name, **tags):
            with span(obs, name, **tags):
                if name != "window.decide":
                    yield
                    return
                state["guard"] = True
                try:
                    with jax.transfer_guard_device_to_host("disallow"):
                        yield
                finally:
                    state["guard"] = False
        monkeypatch.setattr(Observer, "span", guarded_span)

        wire_nbytes = batched.wire_nbytes

        def readback(wires):
            state["readbacks"] += 1
            state["guard"] = False
            try:
                with jax.transfer_guard_device_to_host("allow"):
                    return wire_nbytes(wires)
            finally:
                state["guard"] = True
        monkeypatch.setattr(batched, "wire_nbytes", readback)

        res = _run(setup, "vafl", "batched", **kw)
        c = res.metrics["counters"]
        assert state["reads"] == 0
        assert state["readbacks"] == c["windows"] > 0
        assert c["uploads_device_codec"] == c["uploads"] \
            == res.comm.model_uploads > 0
        assert plain.metrics["counters"].get("uploads_device_codec", 0) == 0
        assert dataclasses.asdict(res.comm) == dataclasses.asdict(plain.comm)
        assert res.byte_ccr == plain.byte_ccr > 0
        assert [r.global_acc for r in res.records] == \
            [r.global_acc for r in plain.records]


# -------------------------------------------------- buffered aggregation ---

def _rand_tree(key, scale=1.0):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (5, 3)) * scale,
            "b": jax.random.normal(k2, (3,)) * scale}


class TestBufferedMix:
    def test_k1_is_async_mix_bitwise(self):
        g = _rand_tree(jax.random.key(0))
        r = _rand_tree(jax.random.key(1))
        a = buffered_mix(g, [r], [0.7], 0.5)
        b = async_mix(g, r, 0.5 * 0.7)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_staleness_weighted_mean(self):
        g = jax.tree.map(jnp.zeros_like, _rand_tree(jax.random.key(0)))
        r1 = jax.tree.map(jnp.ones_like, g)
        r2 = jax.tree.map(lambda x: 3.0 * jnp.ones_like(x), g)
        # s = [1, 3]: recon_bar = (1*1 + 3*3)/4 = 2.5; s_bar = 2; rho=0.25
        out = buffered_mix(g, [r1, r2], [1.0, 3.0], 0.25)
        for leaf in jax.tree.leaves(out):
            np.testing.assert_allclose(np.asarray(leaf), 0.25 * 2.0 * 2.5,
                                       rtol=1e-6)

    def test_batched_window_is_not_a_clock_barrier(self, setup):
        """Window execution batches compute, not the simulated clock:
        sub-full windows keep the sequential engine's idle_fraction
        exactly (clients restart from their own completion times), and
        even the full window stays far below sync-barrier idle (its small
        residual is quota truncation — one event per client per window —
        not barrier waiting)."""
        seq = _run(setup, "afl", "sequential", rounds=4)
        for w in (2, 3):
            bat = _run(setup, "afl", "batched", rounds=4, max_batch=w,
                       buffer_size=2)
            assert bat.idle_fraction == pytest.approx(seq.idle_fraction,
                                                      abs=1e-9)
        full = _run(setup, "afl", "batched", rounds=4, buffer_size=2)
        sync = _run(setup, "fedavg", "sequential", rounds=4)
        assert full.idle_fraction < 0.5 * sync.idle_fraction

    def test_buffered_run_mixes_less_often(self, setup):
        """K=4 buffers arrivals: every upload still counted, convergence
        maintained on the small testbed."""
        res = _run(setup, "afl", "batched", rounds=6, buffer_size=4)
        assert res.comm.model_uploads == 6 * 7     # afl: every event uploads
        assert res.idle_fraction is not None
        assert all(np.isfinite(r.global_acc) for r in res.records)

    def test_buffered_compressed_run(self, setup):
        """Codec payloads + EF ride through the buffered path per-client."""
        res = _run(setup, "vafl", "batched", rounds=6, buffer_size=2,
                   comp="topk0.1_int8")
        assert res.comm.upload_payload_bytes > 0
        assert res.byte_ccr > 0.5
        assert res.comm.model_uploads < 6 * 7      # vafl gates


# ------------------------------------------------------------------ scale ---

@pytest.mark.slow
class TestBatchedEngineScale:
    def test_n256_window_execution(self):
        N = 256
        xtr, ytr, xte, yte = synthetic_mnist(N * 24, 500, seed=0)
        mcfg = MLPConfig(hidden=(32,))
        loss_fn = make_weighted_classifier_loss(mlp_forward, mcfg)
        evaluate = make_evaluator(mlp_forward, mcfg, xte, yte, batch=500)
        fed = iid_partition(xtr, ytr, N, samples_per_client=24, seed=0)
        rc = FLRunConfig(algorithm="afl", num_clients=N, rounds=1,
                         local=LocalSpec(batch_size=32, local_rounds=1,
                                         lr=0.1),
                         target_acc=0.99, events_per_eval=N,
                         engine="batched", buffer_size=16)
        res = run_event_driven(rc,
                               init_params_fn=lambda k: mlp_init(mcfg, k),
                               loss_fn=loss_fn, fed_data=fed,
                               evaluate_fn=evaluate)
        assert res.comm.model_uploads == N         # afl uploads every event
        assert res.comm.broadcasts == N
        assert res.idle_fraction is not None
        assert np.isfinite(res.records[-1].global_acc)


# ----------------------------------------------------- eval fast path ---

class TestEvalFastPath:
    @pytest.mark.parametrize("w", [1, 64, 65, 200, 256])
    def test_window_eval_in_client_chunks_matches_vmap(self, w):
        """Windows wider than the 64-client chunk evaluate a chunk of
        clients per scan step (strided rows, padded when the chunk does
        not divide the window); every client's result equals the plain
        vmap's."""
        from repro.core.runtimes.common import _client_eval_vmap

        def acc(p):
            return jnp.tanh(p["w"] @ p["x"]).sum() + p["b"][0]

        k = jax.random.split(jax.random.key(w), 3)
        stack = {"w": jax.random.normal(k[0], (w, 6, 5)),
                 "x": jax.random.normal(k[1], (w, 5)),
                 "b": jax.random.normal(k[2], (w, 2))}
        got = jax.jit(_client_eval_vmap(acc))(stack)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(jax.vmap(acc)(stack)))

    def test_subsampled_evaluator_deterministic(self, setup):
        """Same subsample seed -> the same test subset -> identical
        scores; a subsample covering the whole set is the full evaluator."""
        _, _, xte, yte, mcfg, _, _, _ = setup
        params = mlp_init(mcfg, jax.random.key(3))
        a = make_evaluator(mlp_forward, mcfg, xte, yte, batch=100,
                           subsample=64, subsample_seed=5)
        b = make_evaluator(mlp_forward, mcfg, xte, yte, batch=100,
                           subsample=64, subsample_seed=5)
        assert float(a(params)) == float(b(params))
        full = make_evaluator(mlp_forward, mcfg, xte, yte, batch=500)
        whole = make_evaluator(mlp_forward, mcfg, xte, yte, batch=500,
                               subsample=len(yte))
        assert float(full(params)) == float(whole(params))

    def test_subsampled_run_records_deterministic(self, setup):
        """Two identical runs under a subsampled client evaluator produce
        identical records (the engine stays seed-reproducible)."""
        _, _, xte, yte, mcfg, loss_fn, evaluate, fed = setup
        sub = make_evaluator(mlp_forward, mcfg, xte, yte, batch=100,
                             subsample=100, subsample_seed=0)
        rc = FLRunConfig(algorithm="vafl", num_clients=7, rounds=3,
                         local=LocalSpec(batch_size=32, local_rounds=1,
                                         lr=0.1),
                         target_acc=0.99, events_per_eval=7,
                         engine="batched", buffer_size=2)
        runs = [run_event_driven(rc,
                                 init_params_fn=lambda k: mlp_init(mcfg, k),
                                 loss_fn=loss_fn, fed_data=fed,
                                 evaluate_fn=evaluate, client_eval_fn=sub)
                for _ in range(2)]
        assert [(r.round, r.global_acc, r.uploads_so_far)
                for r in runs[0].records] == \
               [(r.round, r.global_acc, r.uploads_so_far)
                for r in runs[1].records]

    def test_eval_cache_runs_and_gates(self, setup):
        """eval_cache=3 refreshes each client's Eq. 1 accuracy every 3rd
        own event: the run completes, still gates (vafl uploads < afl's
        every-event count), and records stay finite."""
        res = _run(setup, "vafl", "batched", rounds=6, buffer_size=2,
                   eval_cache=3)
        assert 0 < res.comm.model_uploads < 6 * 7
        assert all(np.isfinite(r.global_acc) for r in res.records)

    def test_eval_cache_zero_is_exact(self, setup):
        """eval_cache=0 (default) is the exact path: bit-identical to a
        run without the knob."""
        a = _run(setup, "vafl", "batched", rounds=4, buffer_size=2)
        b = _run(setup, "vafl", "batched", rounds=4, buffer_size=2,
                 eval_cache=0)
        assert dataclasses.asdict(a.comm) == dataclasses.asdict(b.comm)
        assert [r.global_acc for r in a.records] == \
               [r.global_acc for r in b.records]


# ------------------------------------------------- eval-record cadence ---

class TestEvalCadence:
    def test_window_spanning_boundaries_are_counted(self, setup):
        """events_per_eval boundaries inside one window collapse into a
        single record at window granularity — but every crossed boundary
        is accounted in boundaries_crossed, so cadence math stays exact:
        sum(boundaries_crossed) == total_events // epe."""
        res = _run(setup, "afl", "batched", rounds=4, buffer_size=2,
                   events_per_eval=2)
        total = 4 * 7
        assert sum(r.boundaries_crossed for r in res.records) == total // 2
        # full windows (w=7 > epe=2) must have collapsed several
        assert any(r.boundaries_crossed > 1 for r in res.records)

    def test_sequential_records_one_boundary_each(self, setup):
        res = _run(setup, "afl", "sequential", rounds=2, events_per_eval=2)
        assert all(r.boundaries_crossed == 1 for r in res.records)
        assert len(res.records) == 2 * 7 // 2


# --------------------------------------------- sync barrier participation ---

class TestSyncBarrierParticipation:
    def test_partial_participation_limits_uploads(self, setup):
        _, _, _, _, mcfg, loss_fn, evaluate, fed = setup
        rc = FLRunConfig(algorithm="fedavg", num_clients=7, rounds=3,
                         local=LocalSpec(batch_size=32, local_rounds=1,
                                         lr=0.1),
                         participation=0.5, target_acc=0.99)
        res = run_event_driven(rc,
                               init_params_fn=lambda k: mlp_init(mcfg, k),
                               loss_fn=loss_fn, fed_data=fed,
                               evaluate_fn=evaluate)
        k = max(1, round(0.5 * 7))
        assert res.comm.model_uploads == 3 * k
        assert res.idle_fraction is not None and res.idle_fraction > 0.0

    def test_idle_fraction_is_declared_field(self, setup):
        assert "idle_fraction" in {f.name
                                   for f in dataclasses.fields(RunResult)}
        _, _, _, _, mcfg, loss_fn, evaluate, fed = setup
        rc = FLRunConfig(algorithm="vafl", num_clients=7, rounds=2,
                         local=LocalSpec(batch_size=32, local_rounds=1,
                                         lr=0.1), target_acc=0.99)
        res = run_round_based(rc,
                              init_params_fn=lambda k: mlp_init(mcfg, k),
                              loss_fn=loss_fn, fed_data=fed,
                              evaluate_fn=evaluate)
        assert res.idle_fraction is None   # no simulated clock in round mode
