"""ODAG — Overapproximating Directed Acyclic Graph (paper §5.2).

An ODAG stores a set of same-size canonical embeddings as k per-position
domains plus connectivity bitmaps between consecutive positions: a prefix
tree with all equal-id nodes of a level collapsed. It encodes a *superset*
of the stored embeddings; extraction re-applies the same filters as
Algorithm 1 (validity + canonicality + app filter), which by completeness
removes exactly the spurious paths.

Size: O(k * N^2) bits worst-case vs O(N^k) embeddings — the paper's
several-orders-of-magnitude compression (Fig. 9), reproduced by
``benchmarks/bench_odag.py``.

Two representations:
  * :class:`ODAG` — exact, ragged (host build): per-pattern storage and the
    byte accounting used for Fig. 9.
  * :class:`DenseODAG` — fixed-shape bitmaps over the full vertex space,
    merged across workers with a single OR-allreduce: the distributed
    exchange format (paper §5.2 "merge and broadcast" as one collective).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import canonical
from repro.core.graph import DeviceGraph
from repro.kernels.canonical_check import ops as cc_ops


@dataclasses.dataclass
class ODAG:
    """Exact ragged ODAG for one pattern's embeddings of size k."""

    k: int
    domains: List[np.ndarray]        # level i: (Di,) int32 sorted unique ids
    conn: List[np.ndarray]           # level i: (Di, D_{i+1}) bool

    @property
    def n_bytes(self) -> int:
        b = sum(d.size * 4 for d in self.domains)
        b += sum((c.size + 7) // 8 for c in self.conn)
        return b

    def counts(self) -> List[int]:
        return [len(d) for d in self.domains]

    def path_upper_bound(self) -> int:
        """#paths encoded (incl. spurious): the §5.3 cost estimate."""
        if not self.domains:
            return 0
        cost = np.ones(len(self.domains[-1]), dtype=np.int64)
        for c in reversed(self.conn):
            cost = c @ cost
        return int(cost.sum())


def build(members: np.ndarray, k: Optional[int] = None) -> ODAG:
    """Build the ODAG of a set of size-k embeddings (ids in visit order)."""
    members = np.asarray(members)
    k = k or members.shape[1]
    members = members[:, :k]
    domains, index = [], []
    for i in range(k):
        d = np.unique(members[:, i])
        domains.append(d.astype(np.int32))
        index.append({int(v): j for j, v in enumerate(d)})
    conn = []
    for i in range(k - 1):
        c = np.zeros((len(domains[i]), len(domains[i + 1])), dtype=bool)
        a = np.searchsorted(domains[i], members[:, i])
        b = np.searchsorted(domains[i + 1], members[:, i + 1])
        c[a, b] = True
        conn.append(c)
    return ODAG(k=k, domains=domains, conn=conn)


def partition_by_cost(odag: ODAG, n_workers: int) -> List[np.ndarray]:
    """Paper §5.3: cost-annotated load balancing.

    Each first-level element is annotated with the number of (possibly
    spurious) paths below it; workers take contiguous runs of first-level
    elements with approximately equal total cost. Returns per-worker boolean
    masks over the first-level domain (a worker extracts only paths starting
    at its masked elements). When one element's cost exceeds the target the
    paper splits recursively on the second level; we assign such an element
    to one worker and rebalance the remainder (bounded imbalance, no
    recursion) — the difference only matters for single-hub graphs.
    """
    if not odag.domains:
        return [np.zeros(0, dtype=bool) for _ in range(n_workers)]
    cost = np.ones(len(odag.domains[-1]), dtype=np.int64)
    for c in reversed(odag.conn):
        cost = c @ cost
    total = int(cost.sum())
    target = max(total / max(n_workers, 1), 1.0)
    masks = [np.zeros(len(cost), dtype=bool) for _ in range(n_workers)]
    w, acc = 0, 0.0
    for i, ci in enumerate(np.asarray(cost)):
        if acc >= target and w < n_workers - 1:
            w += 1
            acc = 0.0
        masks[w][i] = True
        acc += float(ci)
    return masks


def extract_partition(g, odag: ODAG, mask: np.ndarray, **kw) -> np.ndarray:
    """Extract only the paths rooted at the masked first-level elements."""
    sub = ODAG(
        k=odag.k,
        domains=[odag.domains[0][mask]] + odag.domains[1:],
        conn=([odag.conn[0][mask]] + odag.conn[1:]) if odag.conn else [],
    )
    return extract(g, sub, **kw)


def merge(odags: List[ODAG]) -> ODAG:
    """Merge worker-local ODAGs of the same pattern (paper's map-reduce edge
    merging, done as set-union + bitmap OR)."""
    k = odags[0].k
    domains = []
    for i in range(k):
        domains.append(
            np.unique(np.concatenate([o.domains[i] for o in odags])).astype(np.int32)
        )
    conn = []
    for i in range(k - 1):
        c = np.zeros((len(domains[i]), len(domains[i + 1])), dtype=bool)
        for o in odags:
            a = np.searchsorted(domains[i], o.domains[i])
            b = np.searchsorted(domains[i + 1], o.domains[i + 1])
            rows, cols = np.nonzero(o.conn[i])
            c[a[rows], b[cols]] = True
        conn.append(c)
    return ODAG(k=k, domains=domains, conn=conn)


@functools.partial(
    jax.jit, static_argnames=("mode", "use_pallas", "interpret")
)
def _extension_mask(g, mem, nv, cnd, *, mode: str, use_pallas: bool,
                    interpret):
    """Algorithm-1 filters of one extraction batch (validity + incremental
    canonicality), one jitted program per batch shape."""
    distinct = ~(mem == cnd[:, None]).any(axis=1)
    if mode == "vertex":
        # validity: adjacency to some member + distinctness
        attach = g.is_edge(mem, cnd[:, None]).any(axis=1)
        if use_pallas:
            canon = cc_ops.canonical_check(
                g, mem, nv, cnd, mode="vertex", interpret=interpret
            )
        else:
            canon = canonical.vertex_check(g, mem, nv, cnd)
    else:
        mu = g.edge_uv[jnp.maximum(mem, 0)]        # (B, k, 2)
        cu = g.edge_uv[jnp.maximum(cnd, 0)]        # (B, 2)
        attach = (
            (mu[..., 0] == cu[:, None, 0])
            | (mu[..., 0] == cu[:, None, 1])
            | (mu[..., 1] == cu[:, None, 0])
            | (mu[..., 1] == cu[:, None, 1])
        ).any(axis=1)
        if use_pallas:
            canon = cc_ops.canonical_check(
                g, mem, nv, cnd, mode="edge", interpret=interpret
            )
        else:
            canon = canonical.edge_check(g, mem, nv, cnd)
    return attach & distinct & canon


def extract(
    g: DeviceGraph,
    odag: ODAG,
    app_filter: Optional[Callable] = None,
    chunk: int = 65536,
    mode: str = "vertex",
    use_pallas: bool = False,
    interpret=None,
) -> np.ndarray:
    """Enumerate the stored embeddings: follow connectivity edges, dropping
    spurious paths with exactly the Algorithm-1 filters (validity +
    incremental canonicality + app filter).

    Returns (B, k) int32. Host-driven loop over levels; each level is a
    vectorised device mask evaluation (same kernels as exploration:
    ``use_pallas`` routes the canonicality re-check through the Pallas
    kernel dispatch, falling back to jnp exactly as the engines do).
    """
    k = odag.k
    paths = odag.domains[0][:, None].astype(np.int32)     # (P, 1)
    for lvl in range(k - 1):
        nxt_dom = odag.domains[lvl + 1]                    # (D,)
        d = len(nxt_dom)
        out = []
        for lo in range(0, len(paths), chunk):
            pc = paths[lo : lo + chunk]                    # (P, lvl+1)
            p = len(pc)
            a = np.searchsorted(odag.domains[lvl], pc[:, lvl])
            mask = odag.conn[lvl][a]                       # (P, D) conn bit
            cand = np.broadcast_to(nxt_dom[None, :], (p, d))

            mem = jnp.asarray(np.repeat(pc, d, axis=0))    # (P*D, lvl+1)
            cnd = jnp.asarray(cand.reshape(-1))
            nv = jnp.full((p * d,), lvl + 1, dtype=jnp.int32)
            valid = _extension_mask(
                g, mem, nv, cnd,
                mode=mode, use_pallas=use_pallas, interpret=interpret,
            )
            keep = np.asarray(valid) & mask.reshape(-1)
            if app_filter is not None:
                keep = keep & np.asarray(app_filter(mem, nv, cnd))
            sel = np.nonzero(keep)[0]
            children = np.concatenate(
                [np.asarray(mem)[sel], np.asarray(cnd)[sel][:, None]], axis=1
            )
            out.append(children)
        paths = np.concatenate(out, axis=0) if out else np.zeros((0, lvl + 2), np.int32)
    return paths.astype(np.int32)


# ---------------------------------------------------------------------------
# Fixed-shape dense ODAG: the distributed exchange format
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DenseODAG:
    """ODAG with domains/connectivity over the full vertex space: fixed
    shapes make it a pytree leaf set that ``psum``/OR-allreduce merges in one
    collective (DESIGN.md §4)."""

    k: int
    domain_bits: jnp.ndarray   # (k, W) uint32 — vertex-in-domain bitmaps
    conn_bits: jnp.ndarray     # (k-1, N, W) uint32 — consecutive-level pairs

    @property
    def n_bytes(self) -> int:
        return int(self.domain_bits.size + self.conn_bits.size) * 4


def build_dense(members: np.ndarray, n_vertices: int, k: int) -> DenseODAG:
    """Scatter rows straight into the packed bitmaps (LSB-first, matching
    :func:`repro.core.bitset.pack_bool_matrix`): no unpacked (N, N) bool
    intermediate, so the host cost is the *packed* O(k·N²/8) bytes the
    exchange format itself costs — not 8x that."""
    members = np.asarray(members)[:, :k]
    w = (n_vertices + 31) // 32
    dom = np.zeros((k, w), dtype=np.uint32)
    conn = np.zeros((max(k - 1, 0), n_vertices, w), dtype=np.uint32)
    for i in range(k):
        v = members[:, i]
        np.bitwise_or.at(dom[i], v // 32, np.uint32(1) << (v % 32).astype(np.uint32))
        if i < k - 1:
            nxt = members[:, i + 1]
            np.bitwise_or.at(
                conn[i],
                (v, nxt // 32),
                np.uint32(1) << (nxt % 32).astype(np.uint32),
            )
    return DenseODAG(
        k=k,
        domain_bits=jnp.asarray(dom),
        conn_bits=jnp.asarray(conn),
    )


def dense_to_ragged(d: DenseODAG) -> ODAG:
    """Unpack a (merged) DenseODAG for extraction."""
    dom_bits = np.asarray(d.domain_bits)
    k, w = dom_bits.shape
    n = np.asarray(d.conn_bits).shape[1] if d.k > 1 else w * 32
    bits = np.unpackbits(
        dom_bits.view(np.uint8).reshape(k, -1), axis=1, bitorder="little"
    )[:, :n]
    domains = [np.nonzero(bits[i])[0].astype(np.int32) for i in range(k)]
    conn = []
    for i in range(k - 1):
        cb = np.asarray(d.conn_bits[i])
        cbits = np.unpackbits(
            cb.view(np.uint8).reshape(n, -1), axis=1, bitorder="little"
        )[:, :n]
        conn.append(cbits[np.ix_(domains[i], domains[i + 1])].astype(bool))
    return ODAG(k=k, domains=domains, conn=conn)
