"""The switching tree the cell's policy must be: fitted here, pinned per cell.

The program fits a depth-``d`` Gini tree on KPMs it profiles with each
expert forced, every slot under interference labelled AI (paper 5.3).
``fit`` is a fit of the same kind written here in NumPy; ``python3 -m
bench.control --pin-tree`` runs it on the program's profile KPMs once and
the result is committed as ``"tree"`` in ``bench/limits/<cell>.json``.
The checks then hold the program's own fitted tree, and every decision of
the window, to that pinned tree.
"""

from __future__ import annotations

import numpy as np


def _gini(counts: np.ndarray) -> np.ndarray:
    """Gini impurity of class counts ``(..., 2)``."""
    n = counts.sum(-1)
    p = counts / np.maximum(n, 1)[..., None]
    return 1.0 - np.sum(p * p, -1)


def _split(x: np.ndarray, y: np.ndarray):
    """``(feature, threshold, decrease)`` of the best split of one node:
    thresholds midway between distinct sorted values, the first feature and
    the lowest threshold on a tie."""
    n = len(y)
    base = _gini(np.bincount(y, minlength=2).astype(np.float64))
    best = (0, np.inf, 0.0)
    for j in range(x.shape[1]):
        order = np.argsort(x[:, j], kind="stable")
        xs = x[order, j]
        left = np.cumsum(np.eye(2)[y[order]], axis=0)[:-1]  # (n-1, 2)
        right = left[-1] + np.eye(2)[y[order[-1]]] - left
        k = np.arange(1, n)
        w = (k * _gini(left) + (n - k) * _gini(right)) / n
        dec = np.where(np.diff(xs) > 0, base - w, -np.inf)
        i = int(np.argmax(dec))
        if dec[i] > best[2] + 1e-12:
            best = (j, 0.5 * (xs[i] + xs[i + 1]), float(dec[i]))
    return best


def fit(x: np.ndarray, y: np.ndarray, depth: int) -> dict:
    """A complete depth-``depth`` tree in level order.  A node that is pure,
    holds fewer than two rows or has no split is a pass-through (threshold
    ``None``, everything left); a leaf takes its rows' majority, or that of
    its nearest ancestor with rows."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.int64)
    n_nodes = 2**depth - 1
    feature = [0] * n_nodes
    threshold = [np.inf] * n_nodes
    rows = {0: np.arange(len(y))}
    for node in range(n_nodes):
        r = rows.get(node, np.arange(0))
        j, t, dec = ((0, np.inf, 0.0) if len(r) < 2
                     or len(np.unique(y[r])) < 2 else _split(x[r], y[r]))
        if np.isfinite(t) and dec > 0:
            feature[node], threshold[node] = j, t
            right = x[r, j] > t
            rows[2 * node + 1], rows[2 * node + 2] = r[~right], r[right]
        else:
            rows[2 * node + 1], rows[2 * node + 2] = r, r[:0]
    leaves = []
    for leaf in range(n_nodes, 2 * n_nodes + 1):
        node = leaf
        while node > 0 and len(rows.get(node, [])) == 0:
            node = (node - 1) // 2
        r = rows.get(node, np.arange(len(y)))
        leaves.append(int(np.bincount(y[r], minlength=2).argmax()))
    return {"depth": depth, "feature": feature,
            "threshold": [float(t) if np.isfinite(t) else None
                          for t in threshold],
            "leaf_values": leaves}


def as_arrays(tree) -> dict:
    """A pinned tree (dict) or the program's fitted tree as arrays."""
    get = (tree.get if isinstance(tree, dict)
           else lambda k: getattr(tree, k))
    thr = [np.inf if t is None else t for t in get("threshold")]
    return {"depth": int(get("depth")),
            "feature": np.asarray(get("feature"), np.int64),
            "threshold": np.asarray(thr, np.float64),
            "leaf_values": np.asarray(get("leaf_values")).astype(np.int64)}


def compare(program_tree, pinned: dict) -> tuple:
    """``(mismatch, threshold_gap)`` of the program's tree against the
    pinned one.  ``mismatch`` counts the nodes that differ in kind: the
    depth, a split's feature, a split on one side only, a leaf's mode.
    ``threshold_gap`` is the widest gap between the two thresholds of a
    split, relative to the pinned one (0 where the tree has no split)."""
    a, b = as_arrays(program_tree), as_arrays(pinned)
    if a["depth"] != b["depth"]:
        return len(b["feature"]) + len(b["leaf_values"]), float("inf")
    ta, tb = a["threshold"], b["threshold"]
    split = np.isfinite(tb)
    kind = (np.isfinite(ta) != split) | (split & (a["feature"]
                                                  != b["feature"]))
    both = split & np.isfinite(ta)
    gap = np.abs(ta[both] - tb[both]) / np.maximum(np.abs(tb[both]), 1e-30)
    mismatch = int(kind.sum() + np.sum(a["leaf_values"] != b["leaf_values"]))
    return mismatch, float(gap.max()) if gap.size else 0.0
