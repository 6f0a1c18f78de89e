"""The benchmark's graph generator: labeled power-law graphs from a seed.

A copy of ``random_labeled`` (Arabesque's evaluation graphs are
scale-free citation and co-authorship networks: vertex weights
``1 / rank**0.75``, uniform labels), kept here so that no change to the
program can change the graphs the benchmark mines. One thing differs:
every seed mines the same wiring, so that every seed fills the padded
``(n, D)`` tables and every chunk of every frontier alike and runs the
same compiled programs.

    edges, labels = random_labeled's draw at the configuration's base_seed
    labels        = a permutation of the label values drawn from ``seed``

A permutation of the label values keeps which vertices share a label, so
every chunk holds as many distinct labeled patterns for every seed, and
the programs sized by those counts are the same. What differs between
seeds is the name of every label, and so the code and the canonical form
of every labeled pattern the job has to find and count.
"""
from __future__ import annotations

import numpy as np


def random_labeled(n: int, m: int, n_labels: int, seed: int,
                   exponent: float = 0.75):
    """``random_labeled``'s draw: labels (n,) uniform over ``n_labels``, and
    (m', 2) sorted unique pairs u < v, m' <= m, endpoints drawn with
    weights ``1 / rank**exponent``."""
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, n + 1) ** exponent
    w /= w.sum()
    us = rng.choice(n, size=int(m * 1.6), p=w)
    vs = rng.choice(n, size=int(m * 1.6), p=w)
    keep = us != vs
    e = np.stack([us[keep], vs[keep]], axis=1)
    e = np.sort(e, axis=1)
    e = np.unique(e, axis=0)
    if len(e) > m:
        idx = rng.choice(len(e), size=m, replace=False)
        e = e[np.sort(idx)]
    labels = rng.integers(0, n_labels, size=n)
    return labels.astype(np.int64), e.astype(np.int64)


def generate(spec: dict, seed: int):
    """(labels (n,) int32, edges (m, 2) int32 sorted unique u < v) of the
    configuration ``spec`` (its ``graph`` block) for one run's ``seed``."""
    n_labels = int(spec["labels"])
    labels, edges = random_labeled(
        int(spec["vertices"]), int(spec["edges"]), n_labels,
        int(spec["base_seed"]), float(spec.get("degree_exponent", 0.75)))
    perm = np.random.default_rng(np.random.SeedSequence(int(seed))).permutation(
        n_labels)
    return perm[labels].astype(np.int32), edges.astype(np.int32)


def degrees(n: int, edges: np.ndarray) -> np.ndarray:
    deg = np.zeros(n, np.int64)
    np.add.at(deg, edges[:, 0], 1)
    np.add.at(deg, edges[:, 1], 1)
    return deg
