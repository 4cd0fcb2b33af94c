"""The comparison that decides ``correct`` for a closed-loop cell.

Every number compares one side (the program, or the control in its place)
with the reference after the first engine window, and every one is a gap of
norms taken leaf by leaf: |‖a‖ - ‖r‖| / max(‖r‖, the median leaf's ‖r‖).
A leaf whose reference norm is under a thousandth of the median leaf's
moves by round-off alone and is left out.  A local round at lr 0.1 can
diverge: a NaN on both sides agrees, a NaN on one side fails.  Each number
is the worst over what it covers:

* ``update_gap``   each client's effective gradient (start - end) / lr, the
                   local round's result, over clients and leaves;
* ``value_gap``    each client's Eq. 1 value, relative to the reference's;
* ``gate_flips``   upload decisions that differ from the reference's where
                   the reference's value lies outside the tie margin around
                   the running mean (exact: limit 0);
* ``residual_gap`` each upload's error-feedback residual (what top-k + int8
                   left out), over uploads and leaves;
* ``global_gap``   the global model's change over the window, after the
                   FedBuff flushes, over leaves.
"""
from __future__ import annotations

import jax
import numpy as np

SKIP_BELOW = 1e-3


def _leaf_norms(tree) -> np.ndarray:
    return np.array([np.linalg.norm(np.asarray(x, np.float64).ravel())
                     for x in jax.tree.leaves(tree)])


def norm_gap(side, ref, where=None) -> float:
    """Worst per-leaf gap of norms between two trees of one structure.
    ``where`` (a dict) receives the worst leaf's index and both norms."""
    a, r = _leaf_norms(side), _leaf_norms(ref)
    # a local round at lr 0.1 can diverge; a leaf that is NaN on both
    # sides agrees, a leaf that is NaN on one side only does not
    both = np.isnan(a) & np.isnan(r)
    if (np.isnan(a) ^ np.isnan(r)).any():
        return float("inf")
    idx = np.flatnonzero(~both)
    a, r = a[idx], r[idx]
    if not len(r):
        return 0.0
    med = float(np.median(r))
    if med == 0.0:                  # nothing moved in the reference
        return 0.0 if not a.any() else float("inf")
    keep = r >= SKIP_BELOW * med
    gaps = np.where(keep, np.abs(a - r) / np.maximum(r, med), 0.0)
    k = int(np.argmax(gaps))
    if where is not None:
        where.update(leaf=int(idx[k]), side=float(a[k]), ref=float(r[k]),
                     median=med)
    return float(gaps[k])


def _worst(pairs, where):
    """The worst ``norm_gap`` over (key, side, ref) triples."""
    best, best_where = -1.0, {}
    for key, a, r in pairs:
        w = {}
        g = norm_gap(a, r, w)
        if g > best:
            best, best_where = g, dict(w, key=key)
    if where is not None:
        where.update(best_where)
    return best


def numbers(side: dict, ref: dict, detail=None) -> dict:
    """The compared numbers of one side against the reference.
    ``detail`` (a dict) receives, for each number, where it was worst."""
    d = {} if detail is None else detail
    n = jax.tree.leaves(ref["eff"])[0].shape[0]
    row = lambda t, c: jax.tree.map(lambda x: x[c], t)  # noqa: E731
    out = {"update_gap": _worst(
        ((c, row(side["eff"], c), row(ref["eff"], c)) for c in range(n)),
        d.setdefault("update_gap", {}))}
    if "values" in ref:
        v, r = np.asarray(side["values"]), np.asarray(ref["values"])
        with np.errstate(divide="ignore", invalid="ignore"):
            rel = np.where(v == r, 0.0, np.abs(v - r) / np.abs(r))
        rel = np.where(np.isnan(v) & np.isnan(r), 0.0,
                       np.where(np.isnan(rel), np.inf, rel))
        out["value_gap"] = float(np.max(rel))
        d["value_gap"] = {"client": int(np.argmax(rel))}
        out["gate_flips"] = len(ref["flips"])
    if ref["residuals"]:
        both = sorted(set(side["residuals"]) & set(ref["residuals"]))
        out["residual_gap"] = (_worst(
            ((c, side["residuals"][c], ref["residuals"][c]) for c in both),
            d.setdefault("residual_gap", {})) if both else 1.0)
    out["global_gap"] = norm_gap(side["global_delta"], ref["global_delta"],
                                 d.setdefault("global_gap", {}))
    return out


def judge(found: dict, limits: dict):
    """(correct, [(name, number, limit)]): every number at or under its
    limit; a number without a limit, or a limit without a number, fails."""
    rows = [(k, found.get(k), limits[k]) for k in sorted(limits)]
    extra = sorted(set(found) - set(limits))
    rows += [(k, found[k], None) for k in extra]
    ok = all(v is not None and lim is not None and np.isfinite(v)
             and v <= lim for _, v, lim in rows)
    return ok, rows
