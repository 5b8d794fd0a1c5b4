"""The benchmark's own Gini fit (``bench.harness.tree``) and the comparison
of a fitted tree with the pinned one."""

import dataclasses

import numpy as np
import pytest

from bench.harness import tree


def data(seed, n=240, f=6):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, f)).astype(np.float32)
    y = ((x[:, 2] > 0.3) | ((x[:, 4] < -0.5) & (x[:, 1] > 0))).astype(int)
    flip = rng.random(n) < 0.05
    return x, np.where(flip, 1 - y, y)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_fit_agrees_with_the_programs_fit(seed, depth):
    from repro.core.policy import fit_decision_tree

    x, y = data(seed)
    ours = tree.fit(x, y, depth)
    theirs = fit_decision_tree(x, y, depth=depth)
    assert tree.compare(theirs, ours)[0] == 0
    assert tree.compare(theirs, ours)[1] < 1e-6


def test_one_class_is_one_leaf():
    x, _ = data(3)
    t = tree.fit(x, np.ones(len(x), int), 2)
    assert t["threshold"] == [None, None, None]
    assert t["leaf_values"] == [1, 1, 1, 1]


def test_compare_counts_what_differs():
    from repro.core.policy import fit_decision_tree

    x, y = data(4)
    pinned = tree.fit(x, y, 2)
    t = fit_decision_tree(x, y, depth=2)
    assert tree.compare(t, pinned) == (0, tree.compare(t, pinned)[1])
    moved = dataclasses.replace(t, threshold=t.threshold * 1.01)
    assert tree.compare(moved, pinned)[1] == pytest.approx(0.01, rel=1e-3)
    flipped = dataclasses.replace(t, leaf_values=1 - t.leaf_values)
    assert tree.compare(flipped, pinned)[0] == 4
    other = dataclasses.replace(t, feature=(t.feature + 1) % 6)
    assert tree.compare(other, pinned)[0] >= 1
    assert tree.compare(dataclasses.replace(t, depth=3), pinned)[0] > 0
