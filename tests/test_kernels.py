"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.grad_diff_norm import ops as gd_ops, ref as gd_ref
from repro.kernels.grad_diff_norm.kernel import grad_diff_sq_norm_2d
from repro.kernels.linear_scan import kernel as ls_kernel, ops as ls_ops, ref as ls_ref


def key(i):
    return jax.random.key(i)


# ------------------------------------------------------- grad_diff_norm ---

@pytest.mark.parametrize("m", [256, 512, 2048])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grad_diff_norm_2d_sweep(m, dtype):
    a = jax.random.normal(key(0), (m, 128), dtype)
    b = jax.random.normal(key(1), (m, 128), dtype)
    got = float(grad_diff_sq_norm_2d(a, b))
    want = float(gd_ref.grad_diff_sq_norm_2d(a, b))
    np.testing.assert_allclose(got, want, rtol=1e-3 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("shapes", [
    [(17,), (33, 5)], [(1000, 37)], [(4,), (4,), (4,)], [(100_001,)],
])
def test_grad_diff_norm_tree_padding(shapes):
    ta = {f"p{i}": jax.random.normal(key(i), s) for i, s in enumerate(shapes)}
    tb = {f"p{i}": jax.random.normal(key(100 + i), s) for i, s in enumerate(shapes)}
    got = float(gd_ops.tree_grad_diff_sq_norm(ta, tb))
    want = float(gd_ref.tree_grad_diff_sq_norm(ta, tb))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_communication_value_epilogue():
    ta = {"w": jnp.ones(100)}
    tb = {"w": jnp.zeros(100)}
    got = float(gd_ops.communication_value(ta, tb, 0.7, 42))
    want = float(gd_ref.communication_value(ta, tb, 0.7, 42))
    np.testing.assert_allclose(got, want, rtol=1e-6)


# ------------------------------------------------------- flash attention ---

@pytest.mark.parametrize("S,bq,bk", [(128, 64, 64), (256, 128, 64), (256, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, bq, bk, dtype):
    BH, D = 3, 64
    q = jax.random.normal(key(0), (BH, S, D), dtype)
    k = jax.random.normal(key(1), (BH, S, D), dtype)
    v = jax.random.normal(key(2), (BH, S, D), dtype)
    got = flash_attention(q, k, v, bq=bq, bk=bk)
    want = fa_ref.attention(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [32, 64])
def test_flash_attention_sliding_window(window):
    BH, S, D = 2, 128, 32
    q = jax.random.normal(key(3), (BH, S, D))
    k = jax.random.normal(key(4), (BH, S, D))
    v = jax.random.normal(key(5), (BH, S, D))
    got = flash_attention(q, k, v, bq=64, bk=64, window=window)
    want = fa_ref.attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_gqa_wrapper_matches_model_layout():
    B, S, H, KV, hd = 2, 128, 4, 2, 32
    q = jax.random.normal(key(6), (B, S, H, hd))
    k = jax.random.normal(key(7), (B, S, KV, hd))
    v = jax.random.normal(key(8), (B, S, KV, hd))
    got = fa_ops.gqa_flash_attention(q, k, v, bq=64, bk=64)
    kr = jnp.repeat(k, H // KV, 2)
    vr = jnp.repeat(v, H // KV, 2)
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    want = fa_ref.attention(to_bh(q), to_bh(kr), to_bh(vr))
    want = want.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


# ----------------------------------------------------------- linear scan ---

@pytest.mark.parametrize("S,chunk", [(64, 32), (128, 64), (128, 128)])
@pytest.mark.parametrize("form", ["mamba", "rwkv"])
def test_linear_scan_sweep(S, chunk, form):
    BH, K, Vd = 4, 16, 8
    q = jax.random.normal(key(0), (BH, S, K))
    k = jax.random.normal(key(1), (BH, S, K))
    v = jax.random.normal(key(2), (BH, S, Vd))
    la = -jnp.abs(jax.random.normal(key(3), (BH, S, K))) * 0.2
    if form == "mamba":
        got = ls_kernel.linear_scan(q, k, v, la, chunk=chunk)
        want = ls_ref.linear_scan(q, k, v, la)
    else:
        u = jnp.abs(jax.random.normal(key(4), (BH, K)))
        got = ls_kernel.linear_scan(q, k, v, la, u, chunk=chunk,
                                    include_current=False)
        want = ls_ref.linear_scan(q, k, v, la, u, include_current=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_linear_scan_dtypes(dtype):
    BH, S, K, Vd = 2, 64, 8, 8
    q = jax.random.normal(key(5), (BH, S, K), dtype)
    k = jax.random.normal(key(6), (BH, S, K), dtype)
    v = jax.random.normal(key(7), (BH, S, Vd), dtype)
    la = (-jnp.abs(jax.random.normal(key(8), (BH, S, K))) * 0.1).astype(dtype)
    got = ls_kernel.linear_scan(q, k, v, la, chunk=32)
    want = ls_ref.linear_scan(q, k, v, la)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_linear_scan_layer_wrapper_matches_model_recurrence():
    """ops.recurrence must agree with the model-side pure-jnp path."""
    from repro.models.recurrence import linear_recurrence
    B, S, H, K, Vd = 2, 64, 2, 8, 8
    q = jax.random.normal(key(9), (B, S, H, K))
    k = jax.random.normal(key(10), (B, S, H, K))
    v = jax.random.normal(key(11), (B, S, H, Vd))
    la = -jnp.abs(jax.random.normal(key(12), (B, S, H, K))) * 0.2
    got = ls_ops.recurrence(q, k, v, la, chunk=32)
    want, _ = linear_recurrence(q, k, v, la, chunk=32, decay_per="dim")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


# ------------------------------------------------------- interpret rule ---

@pytest.mark.parametrize("backend,requested,expected", [
    ("cpu", None, True), ("cpu", False, False), ("cpu", True, True),
    ("tpu", None, False), ("tpu", False, False), ("tpu", True, ValueError),
    ("gpu", None, False),
])
def test_interpret_mode_rule(monkeypatch, backend, requested, expected):
    """One rule for every kernel: compiled unless the backend is the CPU,
    an explicit False always compiles (AOT for a described chip), and
    interpret mode is refused on a TPU backend."""
    from repro.kernels import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expected is ValueError:
        with pytest.raises(ValueError, match="TPU backend"):
            interpret_mode(requested)
    else:
        assert interpret_mode(requested) is expected
