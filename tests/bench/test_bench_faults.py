"""``correct`` has to come out false for each fault the closed-loop cells
can have, planted in the timed path, with the limits the cells run with;
also for a fault in the timed run alone, which the warm-up that the
reference is compared with does not have."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchsmall import run_small  # noqa: E402


def _unchanged_state(loss_fn, spec):
    """A local update that returns the clients' state unchanged."""
    def update(params, data, keys):
        zeros = jax.tree.map(jnp.zeros_like, params)
        return params, zeros, jnp.zeros(keys.shape[0])
    return update


def _half_batch(real):
    """The real local update with the second half of every batch left out
    of the loss, the mean taken over the rest."""
    def make(loss_fn, spec):
        def half(params, batch):
            w = batch["weights"]
            keep = (jnp.arange(w.shape[0]) < w.shape[0] // 2)
            return loss_fn(params, dict(batch, weights=w * keep))
        return real(half, spec)
    return make


def _altered_upload(real):
    """The codec's decoded upload altered where it is produced."""
    def compress(codec, ef, cid, tree, *, seed=0):
        payload, decoded = real(codec, ef, cid, tree, seed=seed)
        return payload, jax.tree.map(lambda x: x * 1.5, decoded)
    return compress


def _in_last_run(real, runs: int):
    """The real local update in the first ``runs - 1`` runs, the unchanged
    state in the last (the timed run)."""
    count = [0]

    def make(loss_fn, spec):
        count[0] += 1
        if count[0] < runs:
            return real(loss_fn, spec)
        return _unchanged_state(loss_fn, spec)
    return make


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_upload", "timed_run_only"])
def test_fault_in_the_timed_path_fails(fault, monkeypatch):
    import repro.core.runtimes.batched as batched
    import repro.core.runtimes.common as common
    if fault == "timed_run_only":
        # one warm-up run (one window at the small size), then the timed run
        monkeypatch.setattr(batched, "make_local_update_keyed",
                            _in_last_run(batched.make_local_update_keyed, 2))
    elif fault == "unchanged_state":
        monkeypatch.setattr(batched, "make_local_update_keyed",
                            _unchanged_state)
    elif fault == "half_batch":
        monkeypatch.setattr(batched, "make_local_update_keyed",
                            _half_batch(batched.make_local_update_keyed))
    else:
        monkeypatch.setattr(common, "compress_update",
                            _altered_upload(common.compress_update))
    line = run_small("paper7_vafl_topk")
    assert line["correct"] is False, line["checks"]
    if fault == "timed_run_only":
        assert line["checks"]["timed_run_mismatches"]["value"] > 0
