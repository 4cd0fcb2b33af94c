"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed with jax, and it compiles for a chip that
is described (``v5e:2x2``) rather than attached, so these tests catch
what interpret mode cannot: block shapes off the (8, 128) tiling,
primitives and casts the kernel compiler does not lower, scalar stores
to vector memory.  Nothing runs; each test asserts the kernel is in the
compiled program (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under pytest-xdist every worker imports this file.  The persistent
compilation cache is off around these compiles (an entry compiled for a
described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.grad_diff_norm.kernel import grad_diff_sq_norm_2d
from repro.kernels.linear_scan.kernel import linear_scan
from repro.kernels.topk_quant.kernel import TILE_M, LANE, topk_quant_2d

# flat parameter counts: the paper's CNN client model (CNNConfig()) and
# an 8M-parameter model
WIDTHS = {"cnn": None, "8M": 8 * 2 ** 20}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _rows(width):
    """(M, 128) rows of the padded flat layout for a parameter count."""
    if width is None:
        from repro.compress.sparsify import flatten_tree
        from repro.models.cnn import CNNConfig, cnn_init
        p = jax.eval_shape(lambda k: cnn_init(CNNConfig(), k),
                           jax.random.key(0))
        width = sum(leaf.size for leaf in jax.tree.leaves(p))
    chunk = TILE_M * LANE
    return -(-width // chunk) * TILE_M


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", list(WIDTHS))
def test_topk_quant_compiles_for_v5e(one_chip, width):
    x = _spec((_rows(WIDTHS[width]), LANE), jnp.float32, one_chip)
    f32 = _spec((), jnp.float32, one_chip)
    seed = _spec((), jnp.uint32, one_chip)
    _assert_kernel(topk_quant_2d.lower(x, f32, f32, seed, interpret=False))


def test_topk_int8_roundtrip_compiles_for_v5e(one_chip, monkeypatch):
    """The codec's device round trip for the CNN as the batched engine
    calls it (rows of 7-client stacks, a residual): one program that
    holds the kernel, under the instruction name by which a device trace
    finds it (``topk_quant_2d``)."""
    import re

    import repro.kernels.topk_quant.kernel as kernel_mod
    from repro.compress import RowDelta, composed
    from repro.models.cnn import CNNConfig, cnn_init

    # the kernel compiled, as on the chip (an explicit False compiles)
    monkeypatch.setattr(kernel_mod, "interpret_mode", lambda i=None: False)
    params = jax.eval_shape(lambda k: cnn_init(CNNConfig(), k),
                            jax.random.key(0))
    tree = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), params)
    stack = jax.tree.map(lambda a: _spec((7,) + a.shape, a.dtype, one_chip),
                         params)
    row = _spec((), jnp.int32, one_chip)
    seed = _spec((), jnp.uint32, one_chip)
    try:
        text = composed.topk_int8_roundtrip.lower(
            RowDelta(stack, row, stack, row), tree, seed, frac=0.1,
            use_kernel=True).compile().as_text()
    finally:
        jax.clear_caches()   # no traced compiled kernel left for the CPU
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1
    assert re.match(r"\s*%topk_quant_2d(\.\d+)? = .*custom-call", calls[0])


@pytest.mark.parametrize("width", list(WIDTHS))
def test_grad_diff_norm_compiles_for_v5e(one_chip, width):
    x = _spec((_rows(WIDTHS[width]), LANE), jnp.float32, one_chip)
    _assert_kernel(grad_diff_sq_norm_2d.lower(x, x, interpret=False))


@pytest.mark.parametrize("form", ["mamba", "rwkv"])
def test_linear_scan_compiles_for_v5e(one_chip, form):
    """RWKV6-3B's wkv shape: 40 heads of 64, a 4096-token sequence."""
    bh, s, k = 40, 4096, 64
    x = _spec((bh, s, k), jnp.float32, one_chip)
    if form == "mamba":
        lowered = linear_scan.lower(x, x, x, x, chunk=64, interpret=False)
    else:
        u = _spec((bh, k), jnp.float32, one_chip)
        lowered = linear_scan.lower(x, x, x, x, u, chunk=64,
                                    include_current=False, interpret=False)
    _assert_kernel(lowered)


def test_flash_attention_compiles_for_v5e(one_chip):
    """StarCoder2-3B's attention: 24 heads of 128, 4096 tokens, bf16."""
    x = _spec((24, 4096, 128), jnp.bfloat16, one_chip)
    _assert_kernel(flash_attention.lower(x, x, x, interpret=False))


def test_window_eval_fits_v5e(one_chip):
    """The batched engine's per-client accuracy term (Eq. 1) over a
    256-client window, CNN at the Federation's default eval_batch of
    500: it used to need ~16.4 GB of scratch, more than a v5e chip's
    16 GB; evaluated 64 clients at a time it must stay well inside."""
    import numpy as np
    from repro.common.pytree import tree_sq_diff_norm
    from repro.core.client import make_evaluator
    from repro.core.runtimes.common import _build_event_helpers
    from repro.models.cnn import CNNConfig, cnn_forward, cnn_init

    cfg = CNNConfig()
    evaluate = make_evaluator(cnn_forward, cfg,
                              np.zeros((10000, 28, 28), np.float32),
                              np.zeros(10000, np.int32), batch=500)
    batch_eval = _build_event_helpers(256, evaluate, tree_sq_diff_norm)[0]
    params = jax.eval_shape(lambda k: cnn_init(cfg, k), jax.random.key(0))
    stack = jax.tree.map(
        lambda a: _spec((256,) + a.shape, a.dtype, one_chip), params)
    mem = batch_eval.lower(stack).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 8e9, mem.temp_size_in_bytes
