"""Value-gated cross-pod collectives — the TPU realisation of VAFL.

In the cross-silo mapping each pod is one federated client ("silo").  The
expensive client->server upload becomes the cross-pod all-reduce of model
deltas; VAFL's gate becomes:

  1. an 8-byte-per-pod all-gather of the scalar communication values V
     (the cheap exchange — Algorithm 1 line 5),
  2. the Eq. 2 mean-threshold mask,
  3. a *masked weighted* psum of the deltas over the "pod" axis, where
     unselected pods contribute zeros (Algorithm 1 line 16).

On real ICI an all-reduce is dense regardless of zeros, so the bytes saved
come from *invocation frequency*: `should_sync` lets the training loop skip
the heavy collective entirely on rounds where no pod clears the threshold,
and the V exchange is O(pods) scalars instead of O(params).  Both effects
are measured by benchmarks/gated_collective.py.

Everything here runs inside ``shard_map`` over the "pod" mesh axis with
``jax.lax`` collectives, so it composes with pjit-sharded per-pod compute.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.value import value_base


def pod_values(grad_prev, grad_cur, acc, n_pods):
    """Per-pod Eq. 1 value, computed locally (no cross-pod traffic)."""
    from repro.common.pytree import tree_sq_diff_norm
    diff = tree_sq_diff_norm(grad_prev, grad_cur)
    return diff * value_base(n_pods) ** jnp.asarray(acc, jnp.float32)


def gated_psum(update, v_local, weight_local, axis_name: str = "pod"):
    """Inside shard_map/pmap over `axis_name`: VAFL-gated weighted average.

    update: local pytree (the pod's model delta); v_local: local scalar V;
    weight_local: local aggregation weight (n_i).  Returns (agg, selected,
    any_selected):  agg = sum_sel(w*u)/sum_sel(w) if any pod is selected,
    else zeros; every pod receives the same agg (psum).
    """
    v_mean = jax.lax.pmean(v_local, axis_name)          # scalar all-reduce
    selected = (v_local >= v_mean).astype(jnp.float32)  # Eq. 2
    w = selected * weight_local.astype(jnp.float32)
    w_tot = jax.lax.psum(w, axis_name)
    any_sel = w_tot > 0

    def agg_leaf(u):
        s = jax.lax.psum(u.astype(jnp.float32) * w, axis_name)
        return jnp.where(any_sel, s / jnp.maximum(w_tot, 1e-9), jnp.zeros_like(s))

    return jax.tree.map(agg_leaf, update), selected, any_sel


def make_gated_allreduce(mesh: Mesh, update_specs, axis_name: str = "pod"):
    """Builds a jitted shard_map'd gated cross-pod aggregation.

    update_specs: PartitionSpec tree for the stacked-update input whose dim0
    is the pod axis.  Input shapes: updates (n_pods, ...), values (n_pods,),
    weights (n_pods,).  Output: aggregated update replicated over pods.
    """
    in_specs = (jax.tree.map(lambda s: P(axis_name, *s), update_specs,
                             is_leaf=lambda x: isinstance(x, P)),
                P(axis_name), P(axis_name))
    out_specs = (jax.tree.map(lambda s: P(*s), update_specs,
                              is_leaf=lambda x: isinstance(x, P)),
                 P(axis_name), P())

    def fn(updates, values, weights):
        local = jax.tree.map(lambda u: u[0], updates)   # (1, ...) -> (...)
        agg, sel, any_sel = gated_psum(local, values[0], weights[0], axis_name)
        return agg, sel[None], any_sel

    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def should_sync(values, axis_name: str = "pod"):
    """Round-level gate: at least one pod above the mean (always True by
    the max>=mean argument unless all values are equal, in which case all
    pods sync — matching Algorithm 1's >= comparison)."""
    v_mean = jax.lax.pmean(values, axis_name)
    return jax.lax.pmax((values >= v_mean).astype(jnp.int32), axis_name) > 0
