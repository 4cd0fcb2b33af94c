"""Update-compression primitives: wire payloads, the Codec protocol, and
the codec registry.

A ``Codec`` maps a parameter/update pytree to a :class:`Payload` — the
exact planes a real client would put on the wire — and back.  Payloads
know their own ``nbytes``, which is what CommStats records, turning the
paper's Eq. 4 CCR from a count ratio into a byte-accurate ratio.

Codecs are lossy (except identity); convergence under loss is restored
by per-client error feedback (repro.compress.error_feedback).  All
encodes are deterministic functions of (tree, seed): stochastic rounding
uses the counter hash shared with the topk_quant kernel, never a global
RNG.

Spec strings accepted by :func:`get_codec` (see docs/COMPRESSION.md):

  "identity" | "none" | ""      no-op, nbytes = full fp32 tree
  "int8" / "int4"               dense stochastic uniform quantization,
                                per-leaf symmetric scale
  "topk" / "topk0.05"           magnitude sparsification, fp32 values +
                                int32 indices (default fraction 0.1)
  "topk_int8" / "topk0.05_int8" composed: top-k then int8 values plane,
                                fused Pallas kernel on the padded
                                (M, 128) layout
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class Payload:
    """What goes on the wire for one compressed transfer.

    ``planes`` are the literal arrays a client would serialize (packed —
    e.g. int4 planes arrive as nibble-packed uint8), ``wire_overhead``
    counts scalar metadata (scales, counts) the planes don't carry, and
    ``meta`` is decode-side state that never ships (treedef, shapes —
    both ends of a real deployment know the model architecture)."""
    codec: str
    planes: Dict[str, np.ndarray]
    meta: Dict[str, Any] = field(default_factory=dict)
    wire_overhead: int = 0

    @property
    def nbytes(self) -> int:
        return int(sum(int(p.nbytes) for p in self.planes.values())
                   + self.wire_overhead)


@dataclass
class DeviceWire:
    """The wire cost of one transfer that a device round trip made
    (``Codec.device_roundtrip``): the planes are never built, only the
    count of kept entries, which stays on the device.  ``nbytes`` (what
    the matching :class:`Payload` would count) reads it back; to read
    many at once, use :func:`wire_nbytes`."""
    kept: jax.Array               # int32 device scalar
    bytes_per_kept: int
    wire_overhead: int = 0

    @property
    def nbytes(self) -> int:
        return int(self.kept) * self.bytes_per_kept + self.wire_overhead


@jax.jit
def _stack_counts(counts):
    return jnp.stack(counts)


def wire_nbytes(wires) -> list:
    """The ``nbytes`` of each of ``wires`` (:class:`Payload` or
    :class:`DeviceWire`), the device wires' counts read back in ONE
    device-to-host copy.  The stack is padded to the next power of two,
    so that the count of wires compiles O(log n) variants."""
    dev = [w.kept for w in wires if isinstance(w, DeviceWire)]
    kept = iter(())
    if dev:
        bucket = 1 << (len(dev) - 1).bit_length()
        kept = iter(np.asarray(_stack_counts(
            tuple(dev + dev[-1:] * (bucket - len(dev))))).tolist())
    return [next(kept) * w.bytes_per_kept + w.wire_overhead
            if isinstance(w, DeviceWire) else w.nbytes for w in wires]


class RowDelta(NamedTuple):
    """An update named by where it lives: row ``crow`` of the stacked
    ``client`` trees minus row ``brow`` of the stacked ``base`` trees (the
    model the client downloaded), in fp32.  A device round trip forms it
    inside its own program; ``tree()`` forms it as a program of its own."""
    base: Any
    brow: Any
    client: Any
    crow: Any

    def tree(self):
        return _row_delta(self)


@jax.jit
def _row_delta(rows: RowDelta):
    return jax.tree.map(
        lambda c, b: (c[rows.crow].astype(jnp.float32)
                      - b[rows.brow].astype(jnp.float32)),
        rows.client, rows.base)


class Codec:
    """encode(tree, seed) -> Payload; decode(Payload) -> tree.

    decode(encode(t)) has the same structure/shapes/dtypes as t; equality
    only holds for identity.  ``seed`` must vary per transfer (the server
    derives it from round/client) so stochastic rounding stays unbiased
    across rounds while each payload remains reproducible.

    A codec may also offer ``device_roundtrip(tree, residual, *, seed)
    -> (decoded, new_residual, DeviceWire)``: one compiled program that
    forms the update (``tree`` may be a :class:`RowDelta`), folds in the
    error-feedback residual (or None), encodes, decodes and forms the new
    residual, reads nothing back to the host, and gives the same bits as
    the ``Payload`` path (``repro.compress.error_feedback
    .compress_update``).  ``None`` where it has none."""
    name: str = "codec"
    is_identity: bool = False
    device_roundtrip = None

    def encode(self, tree, *, seed: int = 0) -> Payload:
        raise NotImplementedError

    def decode(self, payload: Payload):
        raise NotImplementedError

    def roundtrip(self, tree, *, seed: int = 0):
        p = self.encode(tree, seed=seed)
        return p, self.decode(p)


class IdentityCodec(Codec):
    """No-op codec: full fp32 tree on the wire (the uncompressed baseline
    every byte-CCR is measured against)."""
    name = "identity"
    is_identity = True

    def encode(self, tree, *, seed: int = 0) -> Payload:
        leaves, treedef = jax.tree.flatten(tree)
        return Payload(self.name,
                       {f"p{i}": np.asarray(x) for i, x in enumerate(leaves)},
                       meta={"treedef": treedef})

    def decode(self, payload: Payload):
        leaves = [jax.numpy.asarray(payload.planes[f"p{i}"])
                  for i in range(len(payload.planes))]
        return jax.tree.unflatten(payload.meta["treedef"], leaves)


_REGISTRY: Dict[str, Callable[..., Codec]] = {}


def register(name: str):
    def deco(factory):
        _REGISTRY[name] = factory
        return factory
    return deco


_TOPK_RE = re.compile(r"topk(\d*\.?\d+)?(_int8)?$")


def get_codec(spec: Optional[str]) -> Codec:
    """Parse a codec spec string (module docstring grammar) to a Codec."""
    if spec is None or spec in ("", "none", "identity"):
        return IdentityCodec()
    if spec in _REGISTRY:
        return _REGISTRY[spec]()
    m = _TOPK_RE.fullmatch(spec)
    if m:
        frac = float(m.group(1)) if m.group(1) else 0.1
        if not 0.0 < frac <= 1.0:
            raise ValueError(f"top-k fraction out of (0, 1]: {spec!r}")
        factory = _REGISTRY["topk_int8" if m.group(2) else "topk"]
        return factory(frac)
    raise ValueError(f"unknown codec spec {spec!r} "
                     f"(known: identity, int8, int4, topk[frac], "
                     f"topk[frac]_int8)")
