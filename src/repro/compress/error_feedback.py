"""Per-client error-feedback state for lossy update compression.

SGD-EF (Karimireddy et al., "Error Feedback Fixes SignSGD"): the client
keeps the residual e_i = (what it wanted to send) - (what the codec
actually delivered) and folds it into the next update before encoding.
Aggressive codecs (topk at small fractions, int4) then still converge —
dropped mass is delayed, not lost.

State lives client-side in a real deployment; in this single-process
simulation the server runtime owns one ErrorFeedback per run and keys it
by client id.
"""
from __future__ import annotations

from typing import Dict

from repro.common.pytree import tree_add, tree_sub

from repro.compress.base import Codec, RowDelta


class ErrorFeedback:
    """Residual accumulator: apply() folds e_i in, update() re-derives it."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.residuals: Dict[int, object] = {}

    def apply(self, cid: int, tree):
        """update + residual (identity when disabled or first transfer)."""
        if not self.enabled or cid not in self.residuals:
            return tree
        return tree_add(tree, self.residuals[cid])

    def update(self, cid: int, target, decoded):
        """Store e_i = target - decoded for the client's next transfer."""
        if self.enabled:
            self.residuals[cid] = tree_sub(target, decoded)

    def residual(self, cid: int):
        """e_i as ``apply`` would fold it in, or None."""
        return self.residuals.get(cid) if self.enabled else None

    def store(self, cid: int, residual):
        """Keep e_i that a device round trip formed."""
        if self.enabled:
            self.residuals[cid] = residual


def compress_payload(codec: Codec, ef: ErrorFeedback, cid: int, tree, *,
                     seed: int = 0):
    """One client->server transfer through the wire planes: EF-corrected
    encode + server decode.  Returns (payload, decoded) with ef already
    advanced.  The serve loop's path: a transport ships the planes."""
    target = ef.apply(cid, tree)
    payload = codec.encode(target, seed=seed)
    decoded = codec.decode(payload)
    ef.update(cid, target, decoded)
    return payload, decoded


def compress_update(codec: Codec, ef: ErrorFeedback, cid: int, tree, *,
                    seed: int = 0):
    """One client->server transfer inside one process: the codec's device
    round trip where it has one (one compiled program, the wire cost a
    ``DeviceWire`` whose count stays on the device), else
    ``compress_payload``.  ``tree`` is the update or a ``RowDelta``.
    Returns (wire, decoded) with ef already advanced; ``wire.nbytes`` is
    the same either way."""
    if codec.device_roundtrip is None:
        if isinstance(tree, RowDelta):
            tree = tree.tree()
        return compress_payload(codec, ef, cid, tree, seed=seed)
    decoded, residual, wire = codec.device_roundtrip(
        tree, ef.residual(cid), seed=seed)
    ef.store(cid, residual)
    return wire, decoded
