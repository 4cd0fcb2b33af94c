"""repro.compress: codec round-trips, wire-byte accounting, error
feedback, kernel-vs-ref parity, and the compressed-VAFL system test."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.pytree import tree_bytes, tree_sq_diff_norm, tree_sq_norm
from repro.compress import (ErrorFeedback, IdentityCodec, QuantCodec,
                            TopKCodec, TopKQuantCodec, compress_update,
                            get_codec)
from repro.core.metrics import CommStats
from repro.kernels.topk_quant import ops as tq_ops, ref as tq_ref
from repro.kernels.topk_quant.kernel import topk_quant_2d


def key(i):
    return jax.random.key(i)


def make_tree(seed=0, dtype=jnp.float32):
    return {"w": jax.random.normal(key(seed), (130, 37), dtype),
            "b": jax.random.normal(key(seed + 1), (51,), dtype),
            "s": jax.random.normal(key(seed + 2), (), dtype)}


def rel_err(a, b):
    return float(jnp.sqrt(tree_sq_diff_norm(a, b) /
                          jnp.maximum(tree_sq_norm(a), 1e-12)))


ALL_SPECS = ["identity", "int8", "int4", "topk", "topk0.05", "topk_int8",
             "topk0.05_int8"]


# ---------------------------------------------------------- round trips ---

class TestRoundTrip:
    @pytest.mark.parametrize("spec", ALL_SPECS)
    def test_structure_shapes_dtypes_preserved(self, spec):
        tree = make_tree()
        _, dec = get_codec(spec).roundtrip(tree, seed=3)
        assert jax.tree.structure(dec) == jax.tree.structure(tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(dec)):
            assert a.shape == b.shape and a.dtype == b.dtype

    def test_identity_is_exact(self):
        tree = make_tree()
        p, dec = IdentityCodec().roundtrip(tree)
        assert rel_err(tree, dec) == 0.0
        assert p.nbytes == tree_bytes(tree)

    @pytest.mark.parametrize("bits,tol", [(8, 1.0 / 127), (4, 1.0 / 7)])
    def test_quant_error_bounded_by_step(self, bits, tol):
        """Stochastic rounding moves each entry by < one step = scale."""
        tree = make_tree()
        _, dec = QuantCodec(bits).roundtrip(tree, seed=9)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(dec)):
            step = float(jnp.max(jnp.abs(a))) * tol
            assert float(jnp.max(jnp.abs(a - b))) <= step + 1e-6

    def test_quant_determinism_and_seed_sensitivity(self):
        tree = make_tree()
        c = QuantCodec(8)
        a = c.decode(c.encode(tree, seed=5))
        b = c.decode(c.encode(tree, seed=5))
        assert rel_err(a, b) == 0.0
        c2 = c.decode(c.encode(tree, seed=6))
        assert rel_err(a, c2) > 0.0

    def test_topk_keeps_exactly_k_largest(self):
        tree = make_tree()
        n = sum(x.size for x in jax.tree.leaves(tree))
        codec = TopKCodec(0.1)
        p = codec.encode(tree)
        k = codec.k_of(n)
        assert p.planes["idx"].shape == (k,)
        # the kept magnitudes dominate every dropped magnitude
        flat = np.concatenate([np.ravel(x) for x in jax.tree.leaves(tree)])
        kept = np.zeros(n, bool)
        kept[p.planes["idx"]] = True
        assert np.abs(flat[kept]).min() >= np.abs(flat[~kept]).max()

    def test_topk_int8_matches_topk_support(self):
        """Composed codec keeps (at least) the same top-k support and its
        dequantized values stay within one quantization step."""
        tree = make_tree()
        p = TopKQuantCodec(0.1).encode(tree, seed=4)
        dec = TopKQuantCodec(0.1).decode(p)
        flat = np.concatenate([np.ravel(x) for x in jax.tree.leaves(tree)])
        dflat = np.concatenate([np.ravel(x) for x in jax.tree.leaves(dec)])
        kept = np.zeros(flat.size, bool)
        kept[p.planes["idx"]] = True
        scale = p.meta["scale"]
        assert np.abs(flat[kept] - dflat[kept]).max() <= scale + 1e-6
        assert (dflat[~kept] == 0).all()


# ------------------------------------------------------- byte accounting ---

class TestNbytes:
    def test_topk_wire_size(self):
        tree = make_tree()
        n = sum(x.size for x in jax.tree.leaves(tree))
        codec = TopKCodec(0.05)
        assert codec.encode(tree).nbytes == codec.k_of(n) * (4 + 4)

    def test_topk_int8_wire_size(self):
        tree = make_tree()
        p = TopKQuantCodec(0.1).encode(tree, seed=1)
        k_kept = p.planes["idx"].size
        assert p.nbytes == k_kept * (4 + 1) + 4  # idx + int8 val + scale

    def test_int8_int4_wire_size(self):
        tree = make_tree()
        leaves = jax.tree.leaves(tree)
        n = sum(x.size for x in leaves)
        p8 = QuantCodec(8).encode(tree)
        assert p8.nbytes == n + 4 * len(leaves)
        p4 = QuantCodec(4).encode(tree)
        packed = sum((x.size + 1) // 2 for x in leaves)
        assert p4.nbytes == packed + 4 * len(leaves)

    def test_ratio_ordering(self):
        """The zoo must actually order by aggressiveness on the wire."""
        tree = make_tree()
        sizes = {s: get_codec(s).encode(tree, seed=0).nbytes
                 for s in ("identity", "int8", "int4", "topk0.1",
                           "topk0.1_int8")}
        assert sizes["identity"] > sizes["int8"] > sizes["int4"]
        assert sizes["topk0.1"] > sizes["topk0.1_int8"]
        assert sizes["identity"] >= 4 * sizes["topk0.1_int8"]

    def test_commstats_payload_accounting(self):
        comm = CommStats(model_bytes=1000)
        comm.record_upload(1)                 # uncompressed
        comm.record_upload(1, nbytes=100)     # compressed payload
        assert comm.model_uploads == 2
        assert comm.upload_payload_bytes == 1100
        assert comm.byte_ccr == pytest.approx(1 - 1100 / 2000)
        comm.record_broadcast(2, nbytes=300)
        assert comm.broadcast_payload_bytes == 300
        assert comm.downlink_bytes == 300

    def test_registry_rejects_unknown(self):
        with pytest.raises(ValueError):
            get_codec("gzip")
        with pytest.raises(ValueError):
            get_codec("topk1.5")


# -------------------------------------------------------- error feedback ---

class TestErrorFeedback:
    def test_residual_is_encode_error(self):
        tree = make_tree()
        ef = ErrorFeedback()
        codec = TopKCodec(0.05)
        _, dec = compress_update(codec, ef, 0, tree, seed=1)
        want = jax.tree.map(lambda a, b: a - b, tree, dec)
        assert rel_err(want, ef.residuals[0]) < 1e-6

    def test_disabled_keeps_no_state(self):
        ef = ErrorFeedback(enabled=False)
        compress_update(TopKCodec(0.05), ef, 0, make_tree(), seed=1)
        assert ef.residuals == {}

    def test_ef_recovers_dropped_mass(self):
        """Feeding the same update through an aggressive top-k repeatedly:
        with EF the *cumulative* decoded mass approaches the cumulative
        input (dropped coordinates are delayed, not lost); without EF the
        never-selected coordinates are lost forever."""
        tree = make_tree()
        codec = TopKCodec(0.05)

        def total_decoded(ef):
            tot = jax.tree.map(jnp.zeros_like, tree)
            for r in range(25):
                _, dec = compress_update(codec, ef, 0, tree, seed=r)
                tot = jax.tree.map(jnp.add, tot, dec)
            return tot

        want = jax.tree.map(lambda x: 25.0 * x, tree)
        err_ef = rel_err(want, total_decoded(ErrorFeedback()))
        err_no = rel_err(want, total_decoded(ErrorFeedback(enabled=False)))
        # without EF the never-selected 95% of coordinates never ship;
        # with EF the relative loss is the steady-state residual, which
        # shrinks like 1/rounds instead of staying O(1)
        assert err_no > 0.7
        assert err_ef < err_no / 2

    def test_per_client_isolation(self):
        ef = ErrorFeedback()
        codec = TopKCodec(0.05)
        compress_update(codec, ef, 0, make_tree(0), seed=1)
        compress_update(codec, ef, 1, make_tree(50), seed=1)
        assert set(ef.residuals) == {0, 1}
        assert rel_err(ef.residuals[0], ef.residuals[1]) > 0.0


# ------------------------------------------ device round trip vs Payload ---

def _tied_tree(seed):
    """Five magnitude levels: the k-th largest is tied with many others,
    so more than k entries pass the threshold."""
    levels = jnp.array([-1.0, -0.5, 0.25, 0.5, 1.0])
    t = make_tree(seed)
    return jax.tree.map(
        lambda x: levels[jax.random.randint(key(seed + x.size), x.shape, 0,
                                            5)], t)


def _sparse_tree(seed):
    """Mostly exact zeros: fewer than k nonzeros, so the threshold's clamp
    drops the zeros and fewer than k entries survive."""
    return jax.tree.map(
        lambda x: jnp.where(jax.random.uniform(key(seed + x.size), x.shape)
                            < 0.03, x, 0.0), make_tree(seed))


TREES = {"normal": make_tree, "tied": _tied_tree, "sparse": _sparse_tree}


class TestDeviceRoundTrip:
    """The in-process runtimes' compiled round trip and the serve loop's
    encode -> Payload -> decode give the same bits: reconstruction, new
    error-feedback residual, and wire bytes."""

    @pytest.mark.parametrize("use_kernel", [True, False],
                             ids=["kernel", "oracle"])
    @pytest.mark.parametrize("kind", list(TREES))
    @pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
    def test_matches_payload_path(self, seed, kind, use_kernel):
        from repro.compress import compress_payload, wire_nbytes
        from repro.core.runtimes.common import (_compressed_upload,
                                                _tree_apply_delta,
                                                _tree_delta)
        codec = TopKQuantCodec(0.1, use_kernel=use_kernel)
        base = make_tree(seed % 1000 + 100)   # the model downloaded
        stack1 = lambda t: jax.tree.map(lambda x: x[None], t)  # noqa: E731
        ef_p, ef_d = ErrorFeedback(), ErrorFeedback()
        n = sum(x.size for x in jax.tree.leaves(base))
        k = max(1, round(0.1 * n))
        # the second transfer folds in the first one's residual
        for step in range(2):
            client = jax.tree.map(jnp.add, base, TREES[kind](seed + step))
            enc_seed = seed + 1000 * step
            payload, dec = compress_payload(codec, ef_p, 3,
                                            _tree_delta(client, base),
                                            seed=enc_seed)
            recon, wire = _compressed_upload(codec, ef_d, stack1(base), 0,
                                             stack1(client), 0, 3, enc_seed)
            want = _tree_apply_delta(base, dec)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(recon)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(ef_p.residuals[3]),
                            jax.tree.leaves(ef_d.residuals[3])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert wire.nbytes == payload.nbytes
            assert wire_nbytes([wire, payload]) == [payload.nbytes] * 2
        kept = payload.planes["idx"].size
        assert {"normal": kept >= k, "tied": kept > k,
                "sparse": kept < k}[kind], (kept, k)

    def test_codecs_without_a_round_trip_take_the_payload_path(self):
        for spec in ("identity", "int8", "int4", "topk0.1"):
            assert get_codec(spec).device_roundtrip is None
        wire, _ = compress_update(TopKCodec(0.1), ErrorFeedback(), 0,
                                  make_tree(), seed=1)
        assert wire.planes["idx"].size == TopKCodec(0.1).k_of(
            sum(x.size for x in jax.tree.leaves(make_tree())))


# -------------------------------------------------- kernel vs ref parity ---

class TestTopkQuantKernel:
    @pytest.mark.parametrize("m", [256, 512, 1024])
    @pytest.mark.parametrize("seed", [0, 123456789])
    def test_kernel_matches_ref_bitexact(self, m, seed):
        x = jax.random.normal(key(m), (m, 128))
        thr, scale = tq_ops.topk_threshold_scale(x, m * 128, m * 13)
        qk, mk = topk_quant_2d(x, thr, scale, seed)
        qr, mr = tq_ref.topk_quant_2d(x, thr, scale, jnp.uint32(seed))
        np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
        np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))

    def test_threshold_excludes_padding(self):
        """Padding zeros (masked to -inf in the prologue) must not leak
        into threshold or scale."""
        x = jnp.zeros((256, 128)).at[:2, :].set(
            jax.random.normal(key(7), (2, 128)))
        n_real = 2 * 128
        thr, scale = tq_ops.topk_threshold_scale(x, n_real, 64)
        top = np.sort(np.abs(np.asarray(x[:2].ravel())))[-64]
        assert float(thr) == pytest.approx(top)

    def test_stochastic_round_unbiased(self):
        """E[q * scale] ~= x across seeds (the EF-free unbiasedness that
        makes stochastic quantization converge)."""
        x = jnp.full((256, 128), 0.3)
        acc = np.zeros((256, 128), np.float64)
        n_seeds = 64
        for s in range(n_seeds):
            q, mask = tq_ref.topk_quant_2d(x, jnp.float32(0.0),
                                           jnp.float32(0.1), jnp.uint32(s))
            acc += np.asarray(q, np.float64) * 0.1
        np.testing.assert_allclose(acc / n_seeds, 0.3, atol=0.02)

    def test_multi_device_operand_runs_on_one_device(self):
        """A Pallas kernel cannot be partitioned across devices, so the
        batched engine takes each upload out of client state sharded
        over devices onto one device before the codec's kernel runs on
        it.  4 forced CPU devices, in a subprocess."""
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
            from repro.compress import composed
            from repro.models.cnn import MLPConfig, mlp_forward, mlp_init

            # the codec's kernel runs inside its compiled round trip, on
            # the devices that hold the program's operands
            spans = []
            real = composed.topk_int8_roundtrip

            def spy(tree, residual, *a, **k):
                spans.append(len({d for x in jax.tree.leaves((tree, residual))
                                  for d in x.sharding.device_set}))
                return real(tree, residual, *a, **k)
            composed.topk_int8_roundtrip = spy

            xtr, ytr, xte, yte = synthetic_mnist(8 * 40, 40, seed=0)
            mcfg = MLPConfig(hidden=(8,))
            fed = iid_partition(xtr, ytr, 8, samples_per_client=40, seed=0)
            rc = FLRunConfig(algorithm="afl", num_clients=8, rounds=1,
                             local=LocalSpec(batch_size=20, local_rounds=1,
                                             lr=0.1),
                             compressor="topk0.1_int8", engine="batched",
                             shard_clients=True)
            res = run_event_driven(
                rc, init_params_fn=lambda k: mlp_init(mcfg, k),
                loss_fn=make_weighted_classifier_loss(mlp_forward, mcfg),
                fed_data=fed, evaluate_fn=lambda p: 0.0)
            assert res.comm.model_uploads == 8, res.comm.model_uploads
            assert spans == [1] * 8, spans
            print("OK")
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, cwd=".")
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout

    def test_codec_kernel_and_oracle_paths_agree(self):
        tree = make_tree()
        pk = TopKQuantCodec(0.1, use_kernel=True).encode(tree, seed=11)
        pr = TopKQuantCodec(0.1, use_kernel=False).encode(tree, seed=11)
        np.testing.assert_array_equal(pk.planes["idx"], pr.planes["idx"])
        np.testing.assert_array_equal(pk.planes["val"], pr.planes["val"])
        assert pk.meta["scale"] == pr.meta["scale"]


# ------------------------------------------------------------ system test ---

@pytest.fixture(scope="module")
def fl_setup():
    from repro.core.client import make_evaluator, make_weighted_classifier_loss
    from repro.data.partition import iid_partition
    from repro.data.synthetic import synthetic_mnist
    from repro.models.cnn import MLPConfig, mlp_forward, mlp_init
    xtr, ytr, xte, yte = synthetic_mnist(4000, 1000, seed=0)
    mcfg = MLPConfig(hidden=(64,))
    fed = iid_partition(xtr, ytr, 3, samples_per_client=1000, seed=0)
    loss_fn = make_weighted_classifier_loss(mlp_forward, mcfg)
    evaluate = make_evaluator(mlp_forward, mcfg, xte, yte, batch=500)
    return fed, mcfg, loss_fn, evaluate


def _run_vafl(fl_setup, mode="round", **cfg_kw):
    from repro.core import FLRunConfig, run_event_driven, run_round_based
    from repro.core.client import LocalSpec
    from repro.models.cnn import mlp_init
    fed, mcfg, loss_fn, evaluate = fl_setup
    rc = FLRunConfig(algorithm="vafl", num_clients=3, rounds=15,
                     local=LocalSpec(batch_size=32, local_epochs=1,
                                     local_rounds=1, lr=0.1),
                     target_acc=0.90, events_per_eval=3, **cfg_kw)
    runner = run_round_based if mode == "round" else run_event_driven
    return runner(rc, init_params_fn=lambda k: mlp_init(mcfg, k),
                  loss_fn=loss_fn, fed_data=fed, evaluate_fn=evaluate)


class TestCompressedVAFL:
    def test_topk_int8_uplink_and_accuracy(self, fl_setup):
        """Acceptance: >= 4x uplink-byte reduction vs uncompressed VAFL
        within 2 accuracy points (round-based runtime)."""
        base = _run_vafl(fl_setup)
        comp = _run_vafl(fl_setup, compressor="topk_int8")
        assert comp.comm.model_uploads > 0
        per_upload_base = base.comm.upload_payload_bytes / base.comm.model_uploads
        per_upload_comp = comp.comm.upload_payload_bytes / comp.comm.model_uploads
        assert per_upload_base >= 4 * per_upload_comp
        assert comp.best_acc > base.best_acc - 0.02
        assert comp.byte_ccr > 0.5
        assert base.byte_ccr == 0.0

    def test_event_driven_compressed(self, fl_setup):
        """Async runtime: the compressed run must still reach the 0.90
        target (event-mode accuracy at 15 per-client rounds is noisy, so
        the strict 2-point criterion lives on the round-based test)."""
        comp = _run_vafl(fl_setup, mode="event", compressor="topk_int8")
        assert comp.uploads_to_target is not None
        assert comp.best_acc >= 0.90
        assert comp.byte_ccr > 0.5

    def test_broadcast_compression(self, fl_setup):
        res = _run_vafl(fl_setup, compressor="topk_int8",
                        broadcast_compressor="int8")
        full = res.comm.broadcasts * res.comm.model_bytes
        assert res.comm.broadcast_payload_bytes < 0.5 * full
        assert res.best_acc > 0.88
