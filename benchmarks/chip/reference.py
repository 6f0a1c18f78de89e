"""Plain numpy references of the mining jobs, independent of the program.

Each reference takes the graph as the benchmark generated it (labels and
edge array) and returns what a correct job must produce:

* motifs to size 3: the count of every vertex-induced labeled pattern,
  keyed by :func:`shape_key`;
* cliques to a size: every clique of each size, as sorted vertex rows.

``keyed`` reads the program's pattern codes (its output format: word 0 =
``n_vertices | edge_bits << 4``, pair a < b at bit ``b(b-1)/2 + a``; words
1-2 = 8-bit labels) into the same keys. ``compare_*`` return the numbers
the run's check prints: how many keys or rows differ and the largest gap.
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np


class HostGraph:
    """Adjacency of an undirected graph given as sorted unique (u < v)."""

    def __init__(self, n: int, labels, edges):
        self.n = int(n)
        self.labels = np.asarray(labels, np.int64)
        self.edges = np.asarray(edges, np.int64).reshape(-1, 2)
        u = np.concatenate([self.edges[:, 0], self.edges[:, 1]])
        v = np.concatenate([self.edges[:, 1], self.edges[:, 0]])
        order = np.lexsort((v, u))
        self.indices = v[order]
        self.indptr = np.zeros(self.n + 1, np.int64)
        np.add.at(self.indptr, u + 1, 1)
        self.indptr = np.cumsum(self.indptr)
        self.deg = np.diff(self.indptr)

    @property
    def m(self) -> int:
        return len(self.edges)

    def nbrs(self, v: int) -> np.ndarray:
        return self.indices[self.indptr[v]:self.indptr[v + 1]]


# ---------------------------------------------------------------------------
# pattern keys
# ---------------------------------------------------------------------------

def _decode(code):
    w0, w1, w2 = (int(x) for x in code)
    nv, bits = w0 & 0xF, w0 >> 4
    adj = np.zeros((nv, nv), bool)
    for b in range(1, nv):
        for a in range(b):
            if (bits >> (b * (b - 1) // 2 + a)) & 1:
                adj[a, b] = adj[b, a] = True
    words = [(w1 >> (8 * i)) & 0xFF for i in range(4)]
    words += [(w2 >> (8 * i)) & 0xFF for i in range(4)]
    return nv, adj, [int(x) for x in words[:nv]]


def shape_key(code):
    """A label-aware key of a pattern with at most 3 vertices, named by
    its shape: vertex, edge, wedge (center first), triangle."""
    nv, adj, lab = _decode(code)
    deg = adj.sum(axis=1)
    n_edges = int(deg.sum()) // 2
    if nv == 1:
        return ("v", lab[0])
    if nv == 2 and n_edges == 1:
        return ("e",) + tuple(sorted(lab))
    if nv == 3 and n_edges == 2:
        c = int(np.argmax(deg))
        return ("w", lab[c]) + tuple(sorted(lab[i] for i in range(3) if i != c))
    if nv == 3 and n_edges == 3:
        return ("t",) + tuple(sorted(lab))
    raise ValueError(f"no reference shape for pattern {code}")


def encode_key(key):
    """The program's pattern code of a :func:`shape_key` key."""
    shape, labels = key[0], list(key[1:])
    nv, bits = {"v": (1, 0), "e": (2, 0b1), "w": (3, 0b011),
                "t": (3, 0b111)}[shape]
    w1 = 0
    for i, lab in enumerate(labels):
        w1 |= int(lab) << (8 * i)
    return (nv | bits << 4, w1, 0)


def keyed(patterns) -> dict:
    """The program's ``{code: count}`` table under :func:`shape_key`; two
    codes with one key (a pattern counted twice) is an error."""
    out = {}
    for code, v in patterns.items():
        k = shape_key(code)
        if k in out:
            raise ValueError(f"two patterns share key {k}")
        out[k] = int(v)
    return out


# ---------------------------------------------------------------------------
# references
# ---------------------------------------------------------------------------

def triangles(g: HostGraph) -> np.ndarray:
    """(T, 3) vertex triples u < v < w of every triangle."""
    out = []
    for u, v in g.edges:
        common = np.intersect1d(g.nbrs(u), g.nbrs(v), assume_unique=True)
        for w in common[common > v]:
            out.append((int(u), int(v), int(w)))
    return np.asarray(out, np.int64).reshape(-1, 3)


def motifs3(g: HostGraph, tri=None) -> dict:
    """Vertex-induced labeled pattern counts of sizes 1 to 3."""
    lab = g.labels
    n_lab = int(lab.max()) + 1 if g.n else 1
    out = defaultdict(int)
    for v in range(g.n):
        out[("v", int(lab[v]))] += 1
    for u, v in g.edges:
        out[("e",) + tuple(sorted((int(lab[u]), int(lab[v]))))] += 1
    # per-vertex neighbour label counts give every wedge (open or closed);
    # each triangle then closes one wedge at each of its three centers
    nl = np.zeros((g.n, n_lab), np.int64)
    e = g.edges
    np.add.at(nl, (e[:, 0], lab[e[:, 1]]), 1)
    np.add.at(nl, (e[:, 1], lab[e[:, 0]]), 1)
    for c in range(n_lab):
        rows = nl[lab == c]
        for a in range(n_lab):
            for b in range(a, n_lab):
                if a == b:
                    cnt = (rows[:, a] * (rows[:, a] - 1) // 2).sum()
                else:
                    cnt = (rows[:, a] * rows[:, b]).sum()
                if cnt:
                    out[("w", c, a, b)] += int(cnt)
    for t in (triangles(g) if tri is None else tri):
        tl = [int(lab[x]) for x in t]
        out[("t",) + tuple(sorted(tl))] += 1
        for i in range(3):
            rest = tuple(sorted(tl[j] for j in range(3) if j != i))
            out[("w", tl[i]) + rest] -= 1
    return {k: v for k, v in out.items() if v}


def cliques(g: HostGraph, max_size: int) -> dict:
    """size -> (count, size) lexicographically sorted rows u < v < ... of
    every clique of that size, sizes 1 to ``max_size`` (sizes with none
    left out). Each clique of one size is grown by every common neighbour
    of its members above its last vertex."""
    sets = [set(g.nbrs(v).tolist()) for v in range(g.n)]
    out = {1: np.arange(g.n, dtype=np.int64)[:, None]}
    if max_size >= 2:
        out[2] = g.edges
    if max_size >= 3:
        out[3] = triangles(g)
    rows = out.get(3)
    for k in range(4, max_size + 1):
        grown = [
            tuple(r) + (x,)
            for r in rows.tolist()
            for x in sorted(set.intersection(*(sets[v] for v in r)))
            if x > r[-1]
        ]
        rows = np.asarray(grown, np.int64).reshape(-1, k)
        out[k] = rows
    return {k: sort_rows(v) for k, v in out.items() if len(v)}


def sort_rows(rows) -> np.ndarray:
    """Rows with their entries sorted, in lexicographic row order."""
    r = np.sort(np.asarray(rows, np.int64), axis=1)
    if len(r) == 0:
        return r
    return r[np.lexsort(r.T[::-1])]


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------

def compare_counts(got: dict, want: dict) -> dict:
    """Keys whose counts differ, and the largest absolute gap."""
    keys = set(got) | set(want)
    gaps = [abs(got.get(k, 0) - want.get(k, 0)) for k in keys]
    return {
        "keys_wrong": int(sum(1 for x in gaps if x)),
        "count_gap": int(max(gaps, default=0)),
    }


def compare_rows(got: dict, want: dict) -> dict:
    """Per size, rows the job returned that the reference lacks or the
    reverse (as a multiset difference); ``count_gap`` is the largest gap
    in the number of rows of one size."""
    wrong, gap = 0, 0
    for k in set(got) | set(want):
        a = sort_rows(got[k]) if k in got else np.zeros((0, k), np.int64)
        b = want.get(k, np.zeros((0, k), np.int64))
        gap = max(gap, abs(len(a) - len(b)))
        if a.shape == b.shape and np.array_equal(a, b):
            continue
        ua, ca = np.unique(a, axis=0, return_counts=True)
        ub, cb = np.unique(b, axis=0, return_counts=True)
        ta = {tuple(r): c for r, c in zip(ua.tolist(), ca.tolist())}
        tb = {tuple(r): c for r, c in zip(ub.tolist(), cb.tolist())}
        wrong += sum(abs(ta.get(r, 0) - tb.get(r, 0)) for r in set(ta) | set(tb))
    return {"keys_wrong": int(wrong), "count_gap": int(gap)}
