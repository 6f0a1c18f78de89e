"""The benchmark's references against the program's brute-force oracle
(``core/baselines/bruteforce.py``), which enumerates every connected
vertex set, at test size."""
import itertools

import numpy as np
import pytest

import graphs
import reference
from repro.core import Graph
from repro.core.baselines import bruteforce

#: a wiring per test seed (the generator keeps the wiring of base_seed)
def spec(seed):
    return dict(vertices=40, edges=110, labels=3, base_seed=seed)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_motifs3_equals_bruteforce(seed):
    labels, edges = graphs.generate(spec(seed), seed)
    g = Graph(n=40, labels=labels, edges=edges)
    want = reference.keyed(bruteforce.motif_counts(g, 3))
    got = reference.motifs3(reference.HostGraph(g.n, labels, edges))
    assert got == want


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_cliques_equal_bruteforce(seed):
    labels, edges = graphs.generate(spec(seed), seed)
    g = Graph(n=40, labels=labels, edges=edges)
    adj = {int(v): set() for v in range(g.n)}
    for u, v in edges:
        adj[int(u)].add(int(v))
        adj[int(v)].add(int(u))
    levels = bruteforce.enumerate_vertex_embeddings(g, 5)
    got = reference.cliques(reference.HostGraph(g.n, labels, edges), 5)
    for k in range(1, 6):
        rows = [sorted(e) for e in levels[k]
                if all(b in adj[a] for a, b in itertools.combinations(e, 2))]
        want = reference.sort_rows(np.asarray(rows).reshape(-1, k))
        np.testing.assert_array_equal(got.get(k, np.zeros((0, k))), want)


def test_compare_counts_and_rows():
    assert reference.compare_counts({"a": 1}, {"a": 1}) == {
        "keys_wrong": 0, "count_gap": 0}
    assert reference.compare_counts({"a": 2, "b": 1}, {"a": 1}) == {
        "keys_wrong": 2, "count_gap": 1}
    rows = {2: np.array([[0, 1], [1, 2]])}
    assert reference.compare_rows(rows, {2: reference.sort_rows(rows[2])}) \
        == {"keys_wrong": 0, "count_gap": 0}
    dup = {2: np.array([[1, 0], [0, 1], [1, 2]])}
    assert reference.compare_rows(dup, {2: reference.sort_rows(rows[2])}) \
        == {"keys_wrong": 1, "count_gap": 1}
