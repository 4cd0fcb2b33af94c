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
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.compress.base import Codec, Payload, register
from repro.compress.sparsify import flatten_tree, unflatten_tree
from repro.kernels.topk_quant import ops


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
        n = int(flat.shape[0])
        x2d = ops.pad_2d(flat)
        k = max(1, int(round(self.frac * n)))
        thr, scale = ops.topk_threshold_scale(x2d, n, k)
        q, mask = ops.topk_quant(x2d, thr, scale, seed & 0xFFFFFFFF,
                                 use_kernel=self.use_kernel)
        kept = np.flatnonzero(np.asarray(mask).ravel()).astype(np.int32)
        planes = {"idx": kept, "val": np.asarray(q).ravel()[kept]}
        meta = {"treedef": treedef, "shapes": shapes, "dtypes": dtypes,
                "n": n, "scale": float(scale)}
        return Payload(self.name, planes, meta=meta, wire_overhead=4)

    def decode(self, payload: Payload):
        m = payload.meta
        flat = jnp.zeros(m["n"], jnp.float32).at[
            jnp.asarray(payload.planes["idx"])].set(
            jnp.asarray(payload.planes["val"], jnp.float32) * m["scale"])
        return unflatten_tree(flat, m["treedef"], m["shapes"], m["dtypes"])


register("topk_int8")(TopKQuantCodec)
