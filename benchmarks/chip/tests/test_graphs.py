"""The generator: the same wiring, and so the same n, m and D, for every
seed; the label values permuted by the seed."""
import json
import os

import numpy as np
import pytest

import graphs
from conftest import CHIP

CONFIGS = ("mico-1pct", "citeseer")
SEEDS = [0, 1, 7, 2**31 - 1, 2**31 + 12345, 2**40 + 3]


def spec(name):
    with open(os.path.join(CHIP, "configs", name + ".json")) as f:
        return json.load(f)["graph"]


@pytest.mark.parametrize("name", CONFIGS)
def test_seed_invariant_sizes(name):
    s = spec(name)
    base_labels, base_edges = graphs.generate(s, SEEDS[0])
    labelings = set()
    for seed in SEEDS:
        labels, edges = graphs.generate(s, seed)
        assert len(labels) == s["vertices"]
        assert len(edges) == s["edges"]
        assert (edges[:, 0] < edges[:, 1]).all()
        assert len(np.unique(edges, axis=0)) == len(edges)
        assert labels.min() >= 0 and labels.max() < s["labels"]
        np.testing.assert_array_equal(edges, base_edges)
        # a permutation of the label values: the same vertices share a
        # label, and each label value names as many vertices as one other
        _, first = np.unique(labels, return_index=True)
        perm = np.zeros(s["labels"], np.int64)
        perm[base_labels[first]] = labels[first]
        np.testing.assert_array_equal(perm[base_labels], labels)
        assert sorted(perm) == list(range(s["labels"]))
        labelings.add(labels.tobytes())
    assert len(labelings) == len(SEEDS)


@pytest.mark.parametrize("name,d", [("mico-1pct", 503), ("citeseer", 252)])
def test_published_max_degree(name, d):
    """D of the wiring is that of chip_smoke.py's graphs: 503 and 252."""
    _, edges = graphs.generate(spec(name), 5)
    assert graphs.degrees(spec(name)["vertices"], edges).max() == d


def test_same_seed_same_graph():
    a = graphs.generate(spec("citeseer"), 99)
    b = graphs.generate(spec("citeseer"), 99)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_copy_of_random_labeled():
    """Before the permutation the draw is the program's random_labeled."""
    from repro.core.graph import random_labeled

    g = random_labeled(300, 900, 5, seed=4)
    labels, edges = graphs.random_labeled(300, 900, 5, 4)
    np.testing.assert_array_equal(labels, np.asarray(g.labels))
    np.testing.assert_array_equal(edges, np.asarray(g.edges))
