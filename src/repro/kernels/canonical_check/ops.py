"""Public wrappers: graph-size + backend dispatch for the Alg.-2 kernels.

Dispatch rules (the contract the engine relies on):

  * **VMEM bitmap limit** — the kernel keeps the whole packed adjacency
    bitmap resident in VMEM, so it is only used when ``N * ceil(N/32) * 4``
    bytes fit under :data:`VMEM_BITMAP_LIMIT` (~8k vertices). Larger graphs
    take the pure-jnp path where XLA streams the bitmap from HBM
    (``canonical.vertex_check`` / ``edge_check``).
  * **Fused-expansion limit** — :func:`expand_canonical` additionally keeps
    the padded neighbour table in VMEM; both structures together must fit
    under :data:`VMEM_FUSED_LIMIT`.
  * **interpret auto-detection** — ``interpret=None`` compiles on TPU
    and interprets on CPU (``repro.kernels.dispatch.resolve_interpret``).
  * **edge mode** — there is no edge-mode kernel yet; ``mode="edge"``
    always routes to the jnp ``canonical.edge_check``. Callers go through
    this wrapper anyway so the kernel lands on the edge hot path the day
    it exists.

Batch-shape handling (empty batches, non-multiples of the block size) lives
inside the kernel wrappers themselves — callers never pad.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import bitset, canonical
from repro.core.graph import DeviceGraph
from repro.kernels.dispatch import note_route
from repro.kernels.canonical_check.canonical_check import (
    canonical_check_pallas,
    canonical_check_tiles_pallas,
    expand_canonical_pallas,
)

VMEM_BITMAP_LIMIT = 8 * 2**20   # bytes of adjacency bitmap we allow in VMEM
#: resident tables (bitmap + neighbour) budget for the fused kernel; the
#: per-block temporaries get their own budget via _fused_block_c so the two
#: together stay under the ~16 MB of VMEM.
VMEM_FUSED_LIMIT = 8 * 2**20
FUSED_TEMP_BUDGET = 4 * 2**20   # per-block (block_c, k, k, D) temporaries
FUSED_TEMP_ARRAYS = 6           # ~concurrent 4-byte k*k*D-shaped temps


def fits_vmem(g) -> bool:
    """True when the packed adjacency bitmap is VMEM-resident-sized.
    Graphs without a replicated bitmap (``PartitionedGraph``) never fit —
    their kernel path is the tile-indexed check below."""
    adj = getattr(g, "adj_bits", None)
    return adj is not None and adj.size * 4 <= VMEM_BITMAP_LIMIT


def fits_vmem_fused(g) -> bool:
    """True when bitmap + neighbour table both fit for the fused kernel
    (per-block temporaries are bounded separately by _fused_block_c)."""
    adj = getattr(g, "adj_bits", None)
    return (
        adj is not None
        and (adj.size + g.nbr.size) * 4 <= VMEM_FUSED_LIMIT
    )


def _fused_block_c(k: int, d: int) -> int:
    """Block size keeping the fused kernel's (block_c, k, k, D)-shaped
    temporaries (word gather, adj_mc, cumsum, violation, ...) under
    FUSED_TEMP_BUDGET — high-degree graphs get small blocks instead of
    blowing VMEM after passing the resident-table guard."""
    per_row = FUSED_TEMP_ARRAYS * k * k * d * 4
    return max(1, min(64, FUSED_TEMP_BUDGET // max(per_row, 1)))


def canonical_check(g: DeviceGraph, members, n_valid, cand, *,
                    mode: str = "vertex", block_b=1024, interpret=None):
    """Alg.-2 check: kernel path for VMEM-sized graphs (vertex mode), jnp
    fallback otherwise. Accepts any batch size, including 0."""
    if mode == "edge":
        note_route("canonical_check", "jnp:edge-mode")
        return canonical.edge_check(g, members, n_valid, cand)
    if not fits_vmem(g):
        note_route("canonical_check", "jnp:bitmap-exceeds-vmem")
        return canonical.vertex_check(g, members, n_valid, cand)
    return canonical_check_pallas(
        members, n_valid, cand, g.adj_bits, block_b=block_b, interpret=interpret
    )


def canonical_check_tiles_ref(members, ranks, n_valid, cand, adj_tile):
    """jnp route of the tile-indexed Alg.-2 check, exact kernel contract:
    adjacency read at the members' halo-tile ``ranks`` (< 0 = not in tile =
    not adjacent), order tests on the global ids."""
    b, k = members.shape
    pos = jnp.arange(k)[None, :]
    valid = pos < n_valid[:, None]
    first_ok = jnp.where(n_valid > 0, members[:, 0] < cand, True)
    neigh = (
        bitset.test_bit(adj_tile, ranks, cand[:, None])
        & valid & (members >= 0)
    )
    found_after = jnp.cumsum(neigh.astype(jnp.int32), axis=1) > 0
    found_before = jnp.concatenate(
        [jnp.zeros((b, 1), dtype=bool), found_after[:, :-1]], axis=1
    )
    violation = valid & found_before & (members > cand[:, None])
    return first_ok & ~violation.any(axis=1)


def canonical_check_tiles(members, ranks, n_valid, cand, adj_tile, *,
                          use_pallas: bool = False, block_b=1024,
                          interpret=None):
    """Tile-indexed Alg.-2 dispatch (vertex mode, partitioned layout):
    kernel path when the gathered halo tile is VMEM-resident-sized, jnp
    route otherwise — the halo is frontier-sized, not graph-sized, so the
    kernel stays live on graphs whose full bitmap long overflowed
    :data:`VMEM_BITMAP_LIMIT`."""
    if not use_pallas:
        return canonical_check_tiles_ref(members, ranks, n_valid, cand,
                                         adj_tile)
    if adj_tile.size * 4 > VMEM_BITMAP_LIMIT:
        note_route("canonical_check", "jnp:tile-exceeds-vmem")
        return canonical_check_tiles_ref(members, ranks, n_valid, cand,
                                         adj_tile)
    return canonical_check_tiles_pallas(
        members, ranks, n_valid, cand, adj_tile,
        block_b=block_b, interpret=interpret,
    )


def expand_canonical(g: DeviceGraph, members, n_valid, *, block_c=None,
                     interpret=None):
    """Fused vertex expansion + canonicality (see kernel docstring).

    Returns ``(cand, valid, keep)`` each ``(C, k, D)``. Callers must check
    :func:`fits_vmem_fused` first; oversized graphs raise ValueError.
    ``block_c`` defaults to the VMEM-temporary-bounded size for
    (k, max_degree).
    """
    if not fits_vmem_fused(g):
        raise ValueError(
            "graph too large for the fused VMEM kernel: "
            f"{(g.adj_bits.size + g.nbr.size) * 4} resident bytes > "
            f"{VMEM_FUSED_LIMIT} (use the unfused canonical_check path)"
        )
    if block_c is None:
        block_c = _fused_block_c(members.shape[1], g.max_degree)
    return expand_canonical_pallas(
        members, n_valid, g.nbr, g.adj_bits, block_c=block_c,
        interpret=interpret,
    )
