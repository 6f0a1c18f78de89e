"""Finding cliques (paper Fig. 4c): vertex-induced exploration where the
filter keeps a candidate only if it is connected to *all* current members —
anti-monotonic local pruning (a non-clique can never extend to a clique).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core.api import MiningApp
from repro.core.graph import DeviceGraph


@dataclasses.dataclass
class CliquesApp(MiningApp):
    mode: str = "vertex"
    max_size: int = 4
    wants_patterns: bool = False     # paper §6.3: Cliques skips pattern agg
    collect_embeddings: bool = True

    def filter(self, g: DeviceGraph, members, n_valid, rows, cand):
        """isClique: the new vertex must neighbour every existing member.
        One (Ncand,) gather per member column: an (Ncand, k) gather would
        pad k to a whole lane tile on the device."""
        nv = n_valid[rows]
        ok = jnp.ones(rows.shape, dtype=bool)
        for i in range(members.shape[1]):
            ok &= g.is_edge(members[:, i][rows], cand) | (i >= nv)
        return ok


def maximal_cliques(result, g: DeviceGraph):
    """Post-process a CliquesApp result into MAXIMAL cliques (the paper's
    §2 generalisation): a size-k clique is maximal iff no vertex is adjacent
    to all its members. Vectorised over the collected embeddings."""
    import numpy as np

    from repro.core.bitset import popcount_u32

    out = {}
    adj = jnp.asarray(g.adj_bits)
    for size, emb in sorted(result.embeddings.items()):
        m = jnp.asarray(emb)                    # (B, size)
        # AND of the members' adjacency bitmaps = common-neighbour set
        rows = adj[m]                           # (B, size, W)
        common = rows[:, 0]
        for i in range(1, size):
            common = common & rows[:, i]
        n_common = popcount_u32(common).sum(axis=1)
        maximal = np.asarray(n_common == 0)
        out[size] = np.asarray(emb)[maximal]
    return out
