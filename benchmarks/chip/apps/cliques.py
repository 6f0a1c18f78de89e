"""Clique listing (``CliquesApp``): every clique of 1 to ``max_size``
vertices, as vertex rows per size. No pattern aggregation, no level 2.

The check compares every row of every size, as a multiset."""
from __future__ import annotations

import math

import numpy as np

import reference

#: numbers the check compares, with their limits (exact: 0)
LIMITS = {"keys_wrong": 0, "count_gap": 0}


def make(params):
    from repro.core.apps import CliquesApp
    return CliquesApp(max_size=int(params["max_size"]))


def answer(res):
    return {int(k): np.asarray(v) for k, v in res.embeddings.items()
            if len(v)}


def reference_answer(g, params):
    """(size -> sorted rows of every clique, the parents each level
    expands: the cliques one vertex short of the next size)."""
    k = int(params["max_size"])
    want = reference.cliques(g, k)
    parents = [want.get(s, np.zeros((0, s), np.int64)) for s in range(1, k)]
    return want, parents


def compare(got, want):
    return reference.compare_rows(got, want)


def control(g, params):
    """The reference's cliques, each reported once per order of its
    vertices (k! times): ``(patterns, embeddings)``."""
    want, _ = reference_answer(g, params)
    return {}, {k: np.repeat(rows, math.factorial(k), axis=0)
                for k, rows in want.items()}
