"""Composed top-k + int8 codec backed by the fused Pallas kernel.

The highest-ratio codec in the zoo: magnitude sparsification to frac·n
entries, then stochastic int8 quantization of the surviving values — 5
bytes per kept entry (int32 index + int8 value) vs 4 bytes per entry
uncompressed, i.e. ~8x uplink reduction at the default frac=0.1.

Selection + quantization run as ONE fused pass over the padded (M, 128)
layout (repro.kernels.topk_quant); only the O(k log n) threshold/scale
prologue and the final index compaction happen outside the kernel.  The
abs-threshold gate keeps roughly k entries — ties at the threshold all
survive (more than k), and when the k-th magnitude is 0 the 1e-12 clamp
drops exact zeros (fewer than k) — and the byte accounting reflects the
actual kept count exactly.

Two paths, built from the same quantize and dequantize functions: the
``Payload`` path (``encode`` / ``decode``, the planes a transport
ships), and the device round trip (``device_roundtrip``: the delta, the
error-feedback fold, encode, decode and the new residual as one compiled
program, for the in-process runtimes), which gives the same bits and
reads nothing back to the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.pytree import tree_add, tree_sub
from repro.compress.base import (Codec, DeviceWire, Payload, RowDelta,
                                 register)
from repro.compress.sparsify import flatten_tree, unflatten_tree
from repro.kernels.topk_quant import ops, ref

# wire cost: an int32 index and an int8 value per kept entry, and the
# fp32 scale
_BYTES_PER_KEPT = 5
_WIRE_OVERHEAD = 4


def _quantize(flat, seed, frac: float, use_kernel: bool):
    """Flat fp32 update -> (q int8, mask int8, scale) on the padded
    (M, 128) layout: the top-k threshold and scale, then the fused
    select + quantize kernel."""
    n = int(flat.shape[0])
    x2d = ops.pad_2d(flat)
    k = max(1, int(round(frac * n)))
    thr, scale = ops.topk_threshold_scale(x2d, n, k)
    q, mask = ops.topk_quant(x2d, thr, scale, seed, use_kernel=use_kernel)
    return q, mask, scale


def _dequantize(q, mask, scale, n: int):
    """The padded planes back to the first ``n`` flat fp32 entries."""
    return ref.dequant_2d(q, mask, scale).ravel()[:n]


def _pinned(x, zero):
    """``x`` with each value's rounding fixed: a compiler may contract the
    multiply that made ``x`` into a following add or subtract (one rounding
    where the Payload path, which subtracts stored values, has two).  An
    integer xor with a zero that it cannot prove to be zero stops that."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) ^ zero
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


@functools.partial(jax.jit, static_argnames=("frac", "use_kernel"))
def topk_int8_roundtrip(tree, residual, seed, *, frac: float,
                        use_kernel: bool):
    """One error-feedback transfer as one program: form ``tree`` (a
    RowDelta's rows are sliced here), fold ``residual`` (or None) into it,
    quantize, dequantize, and return (decoded, the new residual target -
    decoded, the kept count)."""
    if isinstance(tree, RowDelta):
        tree = tree.tree()
    target = tree if residual is None else tree_add(tree, residual)
    flat, treedef, shapes, dtypes = flatten_tree(target)
    q, mask, scale = _quantize(flat, seed, frac, use_kernel)
    kept = jnp.sum(mask, dtype=jnp.int32)
    # a count is never negative, so min(kept, 0) is the zero _pinned needs
    decoded = unflatten_tree(
        _pinned(_dequantize(q, mask, scale, flat.shape[0]),
                jnp.minimum(kept, 0)), treedef, shapes, dtypes)
    return decoded, tree_sub(target, decoded), kept


class TopKQuantCodec(Codec):
    """topk(frac) -> stochastic int8 on the values plane, fused.

    The kernel runs compiled on TPU and in Pallas interpret mode on the
    CPU backend (``repro.kernels.interpret_mode``)."""

    def __init__(self, frac: float = 0.1, *, use_kernel: bool = True):
        assert 0.0 < frac <= 1.0, frac
        self.frac = frac
        self.use_kernel = use_kernel
        self.name = f"topk{frac:g}_int8"

    def encode(self, tree, *, seed: int = 0) -> Payload:
        flat, treedef, shapes, dtypes = flatten_tree(tree)
        q, mask, scale = _quantize(flat, seed & 0xFFFFFFFF, self.frac,
                                   self.use_kernel)
        kept = np.flatnonzero(np.asarray(mask).ravel()).astype(np.int32)
        planes = {"idx": kept, "val": np.asarray(q).ravel()[kept]}
        meta = {"treedef": treedef, "shapes": shapes, "dtypes": dtypes,
                "n": int(flat.shape[0]), "size": int(mask.size),
                "scale": float(scale)}
        return Payload(self.name, planes, meta=meta,
                       wire_overhead=_WIRE_OVERHEAD)

    def decode(self, payload: Payload):
        m = payload.meta
        idx = jnp.asarray(payload.planes["idx"])
        q = jnp.zeros(m["size"], jnp.int8).at[idx].set(
            jnp.asarray(payload.planes["val"]))
        mask = jnp.zeros(m["size"], jnp.int8).at[idx].set(1)
        return unflatten_tree(_dequantize(q, mask, m["scale"], m["n"]),
                              m["treedef"], m["shapes"], m["dtypes"])

    def device_roundtrip(self, tree, residual, *, seed: int = 0):
        """``decode(encode(tree + residual))``, the new residual and the
        wire, as one compiled program (``topk_int8_roundtrip``): nothing
        is read back to the host."""
        decoded, new_residual, kept = topk_int8_roundtrip(
            tree, residual, np.uint32(seed & 0xFFFFFFFF), frac=self.frac,
            use_kernel=self.use_kernel)
        return decoded, new_residual, DeviceWire(kept, _BYTES_PER_KEPT,
                                                 _WIRE_OVERHEAD)


register("topk_int8")(TopKQuantCodec)
