"""Vectorised embedding expansion — the inner loop of Algorithm 1.

One exploration step takes a frontier of canonical embeddings (each a row of
vertex ids or edge ids in visit order) and produces every canonical child
obtained by adding one incident vertex/edge, already deduplicated (within the
parent) and filtered by the embedding-canonicality check.

TPU adaptation (see DESIGN.md §2): instead of per-embedding adjacency-list
walks, we materialise a dense padded candidate tensor ``(C, k, D)`` /
``(C, 2k, D)`` from the padded neighbour table and evaluate *all* pruning
rules as fused mask expressions. The engine chunks the frontier so this
tensor stays bounded.

:func:`fused_chunk_step` is the single device pass of the fused superstep
pipeline (DESIGN.md §8): expansion + canonicality + app filter + stream
compaction + the children's quick-pattern codes, so the engines never
upload a wave twice or sync per chunk.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bitset, canonical, pattern as pattern_lib
from repro.core.graph import DeviceGraph, PartitionedGraph
from repro.kernels import aggregate as aggregate_kernel_lib
from repro.kernels import compact as compact_kernel_lib
from repro.kernels import gather as gather_kernel_lib
from repro.kernels.canonical_check import ops as cc_ops
from repro.kernels.dispatch import device_scope, note_route


class TileView(NamedTuple):
    """One chunk's gathered halo of a :class:`PartitionedGraph`
    (DESIGN.md §11): the ascending unique *member* vertices (vertex mode)
    or member-edge endpoints (edge mode) with their neighbour / incident-
    edge / packed-adjacency rows gathered into dense tiles, plus the
    replicated id/label payload. Everything downstream of expansion
    (canonicality, app filters, the children's quick patterns) consumes
    this view instead of a whole-graph table.

    Rows are *tile-local*; columns of ``adj_t`` stay global, so one
    resident endpoint resolves any pairwise adjacency query —
    :meth:`is_edge` tries both sides, and every pair the fused pipeline
    asks about (member↔candidate, child-embedding pairs) has at most one
    non-member vertex."""

    uniq: jnp.ndarray         # (U,) int32 ascending halo ids, pad sentinel n
    labels: jnp.ndarray       # (n,) int32 — replicated
    edge_uv: jnp.ndarray      # (m, 2) int32 — replicated
    edge_labels: jnp.ndarray  # (m,) int32 — replicated
    nbr_t: jnp.ndarray        # (U, D) int32 gathered neighbour rows, pad -1
    nbr_eid_t: jnp.ndarray    # (U, D) int32 incident-edge rows ((U, 0) unused)
    adj_t: jnp.ndarray        # (U, W) uint32 gathered adjacency rows

    @property
    def n(self) -> int:
        return self.labels.shape[0]

    @property
    def m(self) -> int:
        return self.edge_uv.shape[0]

    @property
    def max_degree(self) -> int:
        return self.nbr_t.shape[1]

    def rank(self, v):
        """(tile row of each global id, hit mask). ``uniq`` is ascending
        with sentinel-``n`` padding, so translation is one searchsorted;
        misses return a clipped-safe row with ``hit=False``."""
        v = jnp.asarray(v)
        r = jnp.searchsorted(self.uniq, jnp.clip(v, 0, self.n)).astype(jnp.int32)
        r = jnp.minimum(r, self.uniq.shape[0] - 1)
        return r, (self.uniq[r] == v) & (v >= 0)

    def is_edge(self, u, v):
        """Symmetric O(1) edge query resolved from whichever endpoint is
        tile-resident (False when neither is, or for out-of-range ids) —
        the total-graph contract every generic caller (quick patterns,
        app phi filters) relies on."""
        ru, hu = self.rank(u)
        rv, hv = self.rank(v)
        return (
            bitset.test_bit(self.adj_t, jnp.where(hu, ru, -1), v)
            | bitset.test_bit(self.adj_t, jnp.where(hv, rv, -1), u)
        )


def halo_cap(members_shape, mode: str, n: int) -> int:
    """Static tile capacity for a chunk: the distinct halo can never exceed
    min(member-vertex slots, n), so the pow2 of that bound makes tile
    overflow impossible by construction — no new host syncs, no retry."""
    c, k = members_shape
    slots = c * k * (2 if mode == "edge" else 1)
    # pow2 bucket (config.next_pow2 inlined: runtime.config imports would
    # cycle through the runtime package __init__)
    return 1 << max(0, (max(min(slots, int(n)), 1) - 1).bit_length())


def halo_vertices(g, members, n_valid, mode: str):
    """Flat (possibly duplicated) halo vertex ids of a chunk: the members
    themselves (vertex mode) or the member edges' endpoints (edge mode);
    invalid slots -1."""
    c, k = members.shape
    valid = jnp.arange(k)[None, :] < n_valid[:, None]
    if mode == "vertex":
        return jnp.where(valid, members, -1).reshape(-1)
    verts = g.edge_uv[jnp.maximum(members, 0)].reshape(c, 2 * k)
    return jnp.where(jnp.repeat(valid, 2, axis=1), verts, -1).reshape(-1)


def build_tile_view(
    g: PartitionedGraph,
    members: jnp.ndarray,
    n_valid: jnp.ndarray,
    mode: str,
    *,
    use_pallas: bool = False,
    compact_kernel: bool = False,
    interpret=None,
) -> TileView:
    """The tile-gather stage of the fused pipeline on one process: halo
    unique (presence bitmap + stream compaction, ``kernels/gather.py``)
    followed by row gathers from the shard-stacked tables through the
    global->flat translation of ``PartitionedGraph.flat_index``. The
    shard-map backend builds the same view per worker with collectives in
    place of the flat gather (``runtime/shard.py``)."""
    cap = halo_cap(members.shape, mode, g.n)
    verts = halo_vertices(g, members, n_valid, mode)
    uniq, _ = gather_kernel_lib.halo_unique(
        verts, g.n, cap, use_kernel=compact_kernel, interpret=interpret
    )
    fi, ok = g.flat_index(uniq)
    fi = jnp.where(ok, fi, -1)
    d, w = g.max_degree, g.adj_sh.shape[2]
    nbr_t = gather_kernel_lib.gather_rows(
        g.nbr_sh.reshape(-1, d), fi, -1,
        use_kernel=use_pallas, interpret=interpret,
    )
    if mode == "edge":
        nbr_eid_t = gather_kernel_lib.gather_rows(
            g.nbr_eid_sh.reshape(-1, d), fi, -1,
            use_kernel=use_pallas, interpret=interpret,
        )
        adj_t = jnp.zeros((cap, 1), jnp.uint32)   # edge mode never reads adj
    else:
        nbr_eid_t = jnp.zeros((cap, 0), jnp.int32)
        adj_t = gather_kernel_lib.gather_rows(
            g.adj_sh.reshape(-1, w), fi, 0,
            use_kernel=use_pallas, interpret=interpret,
        )
    return TileView(
        uniq=uniq,
        labels=g.labels,
        edge_uv=g.edge_uv,
        edge_labels=g.edge_labels,
        nbr_t=nbr_t,
        nbr_eid_t=nbr_eid_t,
        adj_t=adj_t,
    )


class Expansion(NamedTuple):
    """Flattened candidate set for one frontier chunk (before compaction)."""

    rows: jnp.ndarray        # (Ncand,) int32 parent row in the chunk
    cand: jnp.ndarray        # (Ncand,) int32 extension vertex / edge id
    keep: jnp.ndarray        # (Ncand,) bool — canonical, deduped, valid
    n_generated: jnp.ndarray  # () int32 raw candidate slots that were valid
    n_canonical: jnp.ndarray  # () int32 survivors of the canonicality check


def expand_vertex(
    g: DeviceGraph,
    members: jnp.ndarray,   # (C, k) int32, pad -1
    n_valid: jnp.ndarray,   # (C,) int32
    *,
    use_pallas: bool = False,
    fused: bool = False,
    interpret=None,
) -> Expansion:
    """Candidates for vertex-induced exploration.

    A candidate slot (c, i, j) is neighbour j of member i of embedding c.
    Kept iff: slot valid; vertex not already a member; this is the *first*
    occurrence (no earlier member is adjacent to it — neighbour lists are
    sorted-unique so within one member's list it appears once); and the
    extended embedding passes the incremental canonicality check.

    ``use_pallas`` routes the canonicality check through the VMEM-resident
    Pallas kernel (``repro.kernels.canonical_check``); ``fused`` addition-
    ally evaluates the validity masks inside the same kernel pass
    (``expand_canonical``), skipping the ``(C, k, k, D)`` HBM intermediate.
    Both fall back to this jnp path when the graph exceeds the VMEM limits.
    """
    if use_pallas and fused:
        if cc_ops.fits_vmem_fused(g):
            with device_scope("expand_canonical"):
                return _expand_vertex_fused(g, members, n_valid, interpret)
        note_route("expand_canonical", "jnp:tables-exceed-vmem")
    with device_scope("gather"):
        c, k = members.shape
        d = g.max_degree
        pos = jnp.arange(k)[None, :]
        member_ok = pos < n_valid[:, None]                      # (C, k)

        tiled = isinstance(g, TileView)
        if tiled:
            # partitioned path: members are halo-resident by construction, so
            # every member-rooted lookup goes through tile ranks while ids stay
            # global (columns of adj_t are global; see TileView)
            ranks, in_tile = g.rank(members)
            mrow = jnp.where(member_ok & in_tile, ranks, -1)     # (C, k)
            cand = jnp.where(
                (member_ok & in_tile)[:, :, None], g.nbr_t[ranks], -1
            )                                                    # (C, k, D)
        else:
            safe = jnp.maximum(members, 0)
            cand = jnp.where(
                member_ok[:, :, None], g.nbr[safe], -1
            )                                                    # (C, k, D)
        slot_ok = cand >= 0

        # not already a member of the embedding
        is_member = (cand[:, :, :, None] == members[:, None, None, :]).any(-1)

        # first-occurrence dedup: drop if an *earlier* member is adjacent
        # to cand.
        if tiled:
            adj_em = bitset.test_bit(
                g.adj_t, mrow[:, :, None, None], cand[:, None, :, :]
            )
        else:
            adj_em = g.is_edge(members[:, :, None, None], cand[:, None, :, :])
        adj_em = adj_em & member_ok[:, :, None, None]    # (C, k_m, k_i, D)
        earlier = (
            jnp.arange(k)[None, :, None, None]
            < jnp.arange(k)[None, None, :, None]
        )
        seen_earlier = (adj_em & earlier).any(axis=1)           # (C, k_i, D)

        valid = slot_ok & ~is_member & ~seen_earlier

        flat_cand = cand.reshape(c * k * d)
        flat_rows = jnp.repeat(jnp.arange(c, dtype=jnp.int32), k * d)
        flat_valid = valid.reshape(c * k * d)

    with device_scope("canonical_check"):
        if tiled:
            canon = cc_ops.canonical_check_tiles(
                members[flat_rows], mrow[flat_rows], n_valid[flat_rows],
                flat_cand, g.adj_t,
                use_pallas=use_pallas, interpret=interpret,
            )
        elif use_pallas:
            canon = cc_ops.canonical_check(
                g, members[flat_rows], n_valid[flat_rows], flat_cand,
                mode="vertex", interpret=interpret,
            )
        else:
            canon = canonical.vertex_check(
                g, members[flat_rows], n_valid[flat_rows], flat_cand
            )
    keep = flat_valid & canon
    return Expansion(
        rows=flat_rows,
        cand=flat_cand,
        keep=keep,
        n_generated=flat_valid.sum().astype(jnp.int32),
        n_canonical=keep.sum().astype(jnp.int32),
    )


def _expand_vertex_fused(g, members, n_valid, interpret=None) -> Expansion:
    """Vertex expansion through the fused ``expand_canonical`` kernel:
    validity + dedup + Alg.-2 in one VMEM pass, flattened to the same
    Expansion contract as the jnp path."""
    c, k = members.shape
    d = g.max_degree
    cand, valid, keep = cc_ops.expand_canonical(
        g, members, n_valid, interpret=interpret
    )
    return Expansion(
        rows=jnp.repeat(jnp.arange(c, dtype=jnp.int32), k * d),
        cand=cand.reshape(c * k * d),
        keep=keep.reshape(c * k * d),
        n_generated=valid.sum().astype(jnp.int32),
        n_canonical=keep.sum().astype(jnp.int32),
    )


def expand_edge(
    g: DeviceGraph,
    members: jnp.ndarray,   # (C, k) int32 edge ids, pad -1
    n_valid: jnp.ndarray,   # (C,) int32
    *,
    use_pallas: bool = False,
    interpret=None,
) -> Expansion:
    """Candidates for edge-induced exploration.

    Endpoint slots: member i contributes endpoints (2i, 2i+1). A candidate
    edge is drawn from the incident-edge list of an endpoint vertex; it is
    kept only at its first producing slot: dropped if an earlier endpoint
    slot holds the same vertex (whole incident list already enumerated) or
    the candidate's other endpoint (edge enumerated from the other side).
    """
    with device_scope("gather"):
        c, k = members.shape
        d = g.max_degree
        k2 = 2 * k
        safe = jnp.maximum(members, 0)
        pos = jnp.arange(k)[None, :]
        member_ok = pos < n_valid[:, None]                       # (C, k)

        verts = g.edge_uv[safe].reshape(c, k2)                   # (C, 2k)
        vert_ok = jnp.repeat(member_ok, 2, axis=1)               # (C, 2k)
        verts = jnp.where(vert_ok, verts, -1)

        if isinstance(g, TileView):
            # partitioned path: member-edge endpoints are the halo, so their
            # incident-edge / neighbour rows come from the gathered tiles
            ranks, in_tile = g.rank(verts)
            ok3 = (vert_ok & in_tile)[:, :, None]
            cand = jnp.where(ok3, g.nbr_eid_t[ranks], -1)        # (C, 2k, D)
            other = jnp.where(ok3, g.nbr_t[ranks], -1)           # (C, 2k, D)
        else:
            safe_v = jnp.maximum(verts, 0)
            cand = jnp.where(vert_ok[:, :, None], g.nbr_eid[safe_v], -1)
            other = jnp.where(vert_ok[:, :, None], g.nbr[safe_v], -1)
        slot_ok = cand >= 0

        is_member = (cand[:, :, :, None] == members[:, None, None, :]).any(-1)

        slot_idx = jnp.arange(k2)
        earlier = slot_idx[None, :, None, None] < slot_idx[None, None, :, None]
        same_vertex = verts[:, :, None, None] == verts[:, None, :, None]
        hits_other = verts[:, :, None, None] == other[:, None, :, :]
        dup = ((same_vertex & earlier).any(axis=1)
               | (hits_other & earlier).any(axis=1))

        valid = slot_ok & ~is_member & ~dup

        flat_cand = cand.reshape(c * k2 * d)
        flat_rows = jnp.repeat(jnp.arange(c, dtype=jnp.int32), k2 * d)
        flat_valid = valid.reshape(c * k2 * d)

    with device_scope("canonical_check"):
        if use_pallas:
            # routed through the kernel dispatch even though edge mode
            # currently always resolves to the jnp check (see ops.py
            # dispatch rules).
            canon = cc_ops.canonical_check(
                g, members[flat_rows], n_valid[flat_rows], flat_cand,
                mode="edge", interpret=interpret,
            )
        else:
            canon = canonical.edge_check(
                g, members[flat_rows], n_valid[flat_rows], flat_cand
            )
    keep = flat_valid & canon
    return Expansion(
        rows=flat_rows,
        cand=flat_cand,
        keep=keep,
        n_generated=flat_valid.sum().astype(jnp.int32),
        n_canonical=keep.sum().astype(jnp.int32),
    )


def compact(
    members: jnp.ndarray,   # (C, k) parents of the chunk
    exp: Expansion,
    keep: jnp.ndarray,      # (Ncand,) final keep mask (after app filter)
    out_cap: int,
    *,
    use_kernel: bool = False,
    interpret=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Gather kept candidates into a dense (out_cap, k+1) child frontier.

    Returns (children, count). ``count`` may exceed ``out_cap``: the caller
    must then retry with a larger capacity (bucketed recompilation).
    ``use_kernel`` routes the keep-mask compaction through the Pallas
    stream-compaction kernel (``kernels/compact.py``, DESIGN.md §8)
    instead of the jnp nonzero gather; both honour the same contract.
    """
    c, k = members.shape
    if use_kernel:
        idx, count = compact_kernel_lib.stream_compact_pallas(
            keep, out_cap, interpret=interpret
        )
    else:
        idx, count = compact_kernel_lib.stream_compact_ref(keep, out_cap)
    rows = exp.rows[idx]
    cand = exp.cand[idx]
    children = jnp.concatenate([members[rows], cand[:, None]], axis=1)
    slot_valid = jnp.arange(out_cap) < count
    children = jnp.where(slot_valid[:, None], children, -1)
    return children, count


@functools.partial(
    jax.jit,
    static_argnames=(
        "mode", "out_cap", "use_pallas", "fused", "interpret", "compact_kernel"
    ),
)
def expand_and_compact(
    g: DeviceGraph,
    members: jnp.ndarray,
    n_valid: jnp.ndarray,
    mode: str,
    out_cap: int,
    use_pallas: bool = False,
    fused: bool = False,
    interpret=None,
    compact_kernel: bool = False,
):
    """Fused expand + canonicality + compaction (no app filter) — used by
    benchmarks and the distributed runtime where the app filter is fused in
    separately."""
    if isinstance(g, PartitionedGraph):
        g = build_tile_view(
            g, members, n_valid, mode,
            use_pallas=use_pallas, compact_kernel=compact_kernel,
            interpret=interpret,
        )
    if mode == "vertex":
        exp = expand_vertex(
            g, members, n_valid,
            use_pallas=use_pallas, fused=fused, interpret=interpret,
        )
    else:
        exp = expand_edge(
            g, members, n_valid, use_pallas=use_pallas, interpret=interpret
        )
    children, count = compact(
        members, exp, exp.keep, out_cap,
        use_kernel=compact_kernel, interpret=interpret,
    )
    return children, count, exp.n_generated, exp.n_canonical


def fused_chunk_step(
    g: DeviceGraph,
    members: jnp.ndarray,   # (C, k) int32 frontier chunk, pad -1
    n_valid: jnp.ndarray,   # (C,) int32
    out_cap: int,
    *,
    mode: str,
    app=None,
    with_patterns: bool = False,
    with_aggregates: bool = False,
    agg_qcap: int = 4096,
    with_local_verts: bool = True,
    use_pallas: bool = False,
    fused: bool = False,
    compact_kernel: bool = False,
    aggregate_kernel: bool = False,
    aggregate_bin: str = "sort",
    interpret=None,
):
    """ONE device pass of the fused superstep pipeline (DESIGN.md §8):
    expansion + canonicality + the app's phi filter + stream compaction +
    (optionally) the children's quick-pattern codes.

    Returns ``(children, count, codes, local_verts, n_generated,
    n_canonical)``. ``count`` is the unclamped kept total (host overflow
    decisions need no recomputation); with ``with_patterns`` the codes/
    local-vertex tables are ``(out_cap, 3)`` / ``(out_cap, 8)`` aligned
    with ``children`` (pad slots inert), else both are 0-row placeholders.

    ``with_aggregates`` (DESIGN.md §10, mutually exclusive with
    ``with_patterns``) additionally bins the children's quick codes into a
    per-chunk level-1 PARTIAL in the same pass and returns the 7-tuple
    ``(children, count, uniq (acap, 3), ucounts (acap,) int32, n_uniq,
    n_generated, n_canonical)`` where ``acap = min(out_cap, agg_qcap)`` —
    the raw code array never leaves the program; the engine folds the
    partials across the stacked-drain window
    (``aggregation.DeviceLevel1``). Bounding the partial at ``agg_qcap``
    keeps the cross-chunk merges O(Q)-sized instead of O(children);
    ``n_uniq`` is unclamped, so a chunk whose distinct count overflows
    ``acap`` is detected at the fold (device-side flag, no extra sync) and
    the engine re-bins from the frontier waves instead.

    Shared by the serial engine's jitted chunk program and the distributed
    worker body under ``shard_map`` — the same program in both runtimes.

    With a :class:`PartitionedGraph` the pass opens with the tile-gather
    stage (DESIGN.md §11): the chunk's halo tiles are gathered once
    (``build_tile_view``) and every downstream consumer — expansion,
    canonicality, the app filter, the children's quick patterns — runs on
    the :class:`TileView`. The tile capacity is a static function of the
    chunk shape, so the output contract (and the engines' drain protocol)
    is unchanged. A pre-built ``TileView`` is also accepted (the shard-map
    worker builds its own view with collectives).

    Every stage runs under its own ``device_scope`` (``tile_gather``,
    ``gather``, ``canonical_check`` or the fused ``expand_canonical``,
    ``app_filter``, ``compact``, ``pack``), so a profiler trace ties each
    device operation of the program to its stage."""
    if isinstance(g, PartitionedGraph):
        with device_scope("tile_gather"):
            g = build_tile_view(
                g, members, n_valid, mode,
                use_pallas=use_pallas, compact_kernel=compact_kernel,
                interpret=interpret,
            )
    if mode == "vertex":
        exp = expand_vertex(
            g, members, n_valid,
            use_pallas=use_pallas, fused=fused, interpret=interpret,
        )
    else:
        exp = expand_edge(
            g, members, n_valid, use_pallas=use_pallas, interpret=interpret
        )
    keep = exp.keep
    if app is not None:
        with device_scope("app_filter"):
            keep = keep & app.filter(g, members, n_valid, exp.rows, exp.cand)
    with device_scope("compact"):
        children, count = compact(
            members, exp, keep, out_cap,
            use_kernel=compact_kernel, interpret=interpret,
        )
    with device_scope("pack"):
        if with_patterns or with_aggregates:
            child_k = members.shape[1] + 1
            child_nv = jnp.where(
                jnp.arange(out_cap) < count, child_k, 0
            ).astype(jnp.int32)
            qp = (
                pattern_lib.quick_pattern_vertex(g, children, child_nv)
                if mode == "vertex"
                else pattern_lib.quick_pattern_edge(g, children, child_nv)
            )
            if with_aggregates:
                uniq, ucounts, _, n_uniq, _ = aggregate_kernel_lib.bin_rows(
                    qp.codes, child_nv > 0, min(out_cap, agg_qcap),
                    use_kernel=aggregate_kernel, interpret=interpret,
                    method=aggregate_bin,
                )
                # the partial crosses chunks as int32: SATURATE at the I32_SAT
                # sentinel instead of wrapping — fold_partial detects the
                # sentinel and the step re-folds wide (DESIGN.md §13)
                ucounts32 = jnp.minimum(
                    ucounts, jnp.int64(aggregate_kernel_lib.I32_SAT)
                ).astype(jnp.int32)
                return (children, count, uniq, ucounts32,
                        n_uniq, exp.n_generated, exp.n_canonical)
            codes = qp.codes
            # only FSM's min-image domains read the local-vertex table; when
            # unused, dropping it from the outputs lets XLA DCE its scatter
            local_verts = (
                qp.local_verts
                if with_local_verts
                else jnp.zeros(
                    (0, pattern_lib.MAX_PATTERN_VERTICES), jnp.int32
                )
            )
        else:
            codes = jnp.zeros((0, 3), jnp.int64)
            local_verts = jnp.zeros(
                (0, pattern_lib.MAX_PATTERN_VERTICES), jnp.int32
            )
        return (children, count, codes, local_verts,
                exp.n_generated, exp.n_canonical)
