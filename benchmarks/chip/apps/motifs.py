"""Motif counting (``MotifsApp``): the count of every vertex-induced
labeled pattern of 1 to ``max_size`` vertices (at most 3, the sizes the
reference counts in closed form).

The answer is the program's pattern table; the check reads its codes into
the reference's shape keys and compares every count."""
from __future__ import annotations

import sys

import numpy as np

import reference

#: numbers the check compares, with their limits (exact: 0)
LIMITS = {"keys_wrong": 0, "count_gap": 0}

#: discovery orders of each shape of at most 3 vertices: the times an
#: expansion without the canonicality filter reaches one embedding
ORDERS = {"v": 1, "e": 2, "w": 4, "t": 6}
SIZES = {"v": 1, "e": 2, "w": 3, "t": 3}


def _max_size(params) -> int:
    k = int(params["max_size"])
    if not 1 <= k <= 3:
        raise ValueError(f"the motifs reference counts 1 to 3 vertices, "
                         f"not {k}")
    return k


def make(params):
    from repro.core.apps import MotifsApp
    return MotifsApp(max_size=_max_size(params))


def answer(res):
    """The pattern table as codes and counts in code order, numpy arrays
    that add nothing for the garbage collector to walk."""
    p = res.patterns
    codes = np.array(list(p), dtype=np.int64).reshape(-1, 3)
    counts = np.fromiter(p.values(), dtype=np.int64, count=len(p))
    order = np.lexsort(codes.T[::-1])
    return {"codes": codes[order], "counts": counts[order]}


def reference_answer(g, params):
    """(expected keyed counts, the parents each level expands)."""
    k = _max_size(params)
    want = {key: n for key, n in reference.motifs3(g).items()
            if SIZES[key[0]] <= k}
    parents = [np.arange(g.n)[:, None], g.edges][:k - 1]
    return want, parents


def compare(got, want):
    table = {tuple(c): n for c, n in
             zip(got["codes"].tolist(), got["counts"].tolist())}
    try:
        keyed = reference.keyed(table)
    except ValueError as e:         # a pattern the reference has no shape for
        print(f"check: {e}", file=sys.stderr)
        return {"keys_wrong": len(table) or 1, "count_gap": 1}
    return reference.compare_counts(keyed, want)


def control(g, params):
    """The reference's answer with every embedding counted once per
    discovery order: ``(patterns, embeddings)`` as the program returns
    them."""
    want, _ = reference_answer(g, params)
    patterns = {reference.encode_key(key): n * ORDERS[key[0]]
                for key, n in want.items()}
    return patterns, {}
