"""Shard-map execution backend: the mesh superstep (paper §5.1/§5.3 on JAX).

The Giraph BSP superstep becomes one jitted ``shard_map`` program per
exploration step, behind the same
:class:`~repro.core.runtime.backend.ExecutionBackend` protocol as the
serial pipeline:

  * expansion + canonicality is *coordination-free* (paper §5.1): each
    worker expands its frontier slice with zero communication — the worker
    body is the SAME fused chunk program the serial backend jits
    (``explore.fused_chunk_step``, DESIGN.md §8), children land in the
    store as capacity-padded device arrays, and the host takes ONE control
    sync per superstep on the exact (unclamped) child counts;
  * pattern aggregation is ONE collective: per-pattern counts and FSM
    domain bitmaps are ``psum``/OR-allreduced (two-level aggregation:
    bytes scale with #patterns, never #embeddings — Table 4 as
    collective-bytes);
  * the frontier between supersteps is owned by the shared store
    subsystem: ``store="raw"`` re-balances broadcast-then-partition
    (paper §5.3, even block slicing); ``store="odag"`` folds each worker's
    children into a fixed-shape DenseODAG, merges the worker bitmaps with
    a bitwise OR — host-side in this single-process runtime, bit-for-bit
    the §5.2 "merge and broadcast" OR-allreduce of a multi-host mesh —
    and re-materialises every worker's slice via cost-annotated
    partitioning (§5.3). Exchange bytes ride ``StepStats.collective_bytes``.
"""
from __future__ import annotations

import functools
import time
from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import aggregation, explore, obs, pattern as pattern_lib
from repro.core.api import MiningApp
from repro.core.graph import PartitionedGraph
from repro.core.runtime import faults as faults_lib
from repro.core.runtime import programs
from repro.core.runtime.backend import ExecutionBackend
from repro.core.runtime.config import next_pow2
from repro.core.store import FrontierStore, make_store
from repro.kernels import aggregate as agg_kernel_lib
from repro.kernels import canonical_refine
from repro.kernels import gather as gather_kernel_lib
from repro.kernels.dispatch import device_scope

def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` as every worker program here uses it: with the
    varying-manual-axes check off, because a worker body may contain a
    ``pallas_call``, which has no rule for that check."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def auto_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis of type ``Auto``.

    ``jax.make_mesh`` builds ``Explicit`` axes, under which the host-side
    reads of a program's sharded outputs (``gn[0]``, a replicated table
    gathered by a sharded slot array) have no unambiguous sharding and
    raise. The backend's programs shard explicitly through ``shard_map``
    anyway, so it runs them on the ``Auto`` view of the same devices."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def mesh_axis_size(mesh: Mesh, axes) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def pad_parts(parts, k: int):
    """Pad variable-length per-worker row blocks to one dense
    ``(W, per, k)`` int32 array (pad value -1) + per-worker counts — THE
    shard-padding convention, shared by the even block split below and the
    store-provided (cost-balanced) parts in the shard-map backend."""
    n = len(parts)
    per = max(max((len(p) for p in parts), default=0), 1)
    padded = np.full((n, per, k), -1, dtype=np.int32)
    counts = np.zeros(n, dtype=np.int32)
    for s, p in enumerate(parts):
        padded[s, : len(p)] = p
        counts[s] = len(p)
    return padded, counts


def partition_frontier(frontier: np.ndarray, n_shards: int):
    """Broadcast-then-partition (paper §5.3): even block split, padded."""
    b, k = frontier.shape
    per = -(-b // n_shards) if b else 1
    return pad_parts(
        [frontier[s * per : (s + 1) * per] for s in range(n_shards)], k
    )


def make_sharded_expand(app: MiningApp, mesh: Mesh, axes=("data",),
                        use_pallas: bool = False, interpret=None,
                        compact_kernel: bool = False,
                        with_patterns: bool = False):
    """One BSP superstep: coordination-free expand over the mesh.

    The worker body is the SAME fused chunk program the serial backend jits
    (``explore.fused_chunk_step``, DESIGN.md §8): expansion + canonicality
    + app filter + stream compaction, and — with ``with_patterns`` — the
    children's quick-pattern codes in the same device pass, so the next
    superstep's aggregation needs no second upload of the frontier.
    """

    mode = app.mode
    spec_in = P(axes)

    @functools.partial(jax.jit, static_argnames=("out_cap",))
    def step(g, members, n_valid, out_cap: int):
        def worker(g, members, n_valid):
            m = members[0]          # shard_map adds the leading shard dim
            nv = n_valid[0]
            with device_scope("fused_chunk"):
                children, count, codes, lv, ngen, ncanon = (
                    explore.fused_chunk_step(
                        g, m, nv, out_cap,
                        mode=mode,
                        app=app,
                        with_patterns=with_patterns,
                        use_pallas=use_pallas,
                        compact_kernel=compact_kernel,
                        interpret=interpret,
                    )
                )
            outs = (children[None], count[None], ngen[None], ncanon[None])
            if with_patterns:
                outs += (codes[None], lv[None])
            return outs

        n_out = 6 if with_patterns else 4
        return shard_map(
            functools.partial(worker, g),
            mesh=mesh,
            in_specs=(spec_in, spec_in),
            out_specs=(spec_in,) * n_out,
        )(members, n_valid)

    return step


def halo_fetch_tile(pg_l, m, nv, *, mode: str, halo: str, axes,
                    w: int, rows: int, n: int,
                    compact_kernel: bool = False, interpret=None):
    """The halo-exchange stage of the partitioned worker body (DESIGN.md
    §11), shared by the mining superstep and the ``trace_sync`` exchange
    probe (``StepStats.t_exchange``): derive the worker's halo — the
    unique vertices its frontier slice touches — and fetch their neighbour
    rows from the owning shards via in-program collectives, returning the
    ``explore.TileView`` the fused chunk program consumes.

      * ``halo="alltoall"``: a position-aligned request matrix (W, H) of
        vertex ids goes through ONE ``all_to_all``; owners gather the
        requested rows from their local shard and a second ``all_to_all``
        returns them. Wire bytes scale with the halo, never the graph.
      * ``halo="gather"``: ragged fallback — ``all_gather`` the full shard
        tables and index locally (bytes scale with the graph; always
        lowers).

    ``w``/``rows``/``n`` are the FULL graph's shard count / padded tile
    rows / vertex count — inside ``shard_map`` the worker-local ``pg_l``
    only sees its own shard's leading dim.
    """
    # static halo capacity (a function of the chunk shape alone):
    # overflow is impossible by construction, so the output contract
    # of the fused step — and the drain protocol — are untouched
    cap = explore.halo_cap(m.shape, mode, n)
    verts = explore.halo_vertices(pg_l, m, nv, mode)
    uniq, _ = gather_kernel_lib.halo_unique(
        verts, n, cap,
        use_kernel=compact_kernel, interpret=interpret,
    )
    ok = uniq < n
    safe = jnp.clip(uniq, 0, n - 1)
    own = jnp.clip(
        jnp.searchsorted(pg_l.part_offsets, safe, side="right") - 1,
        0, w - 1,
    ).astype(jnp.int32)

    if halo == "gather":
        # ragged all-gather fallback: full shard tables on the wire
        fi = jnp.clip(
            own * rows + (safe - pg_l.part_offsets[own]),
            0, w * rows - 1,
        ).astype(jnp.int32)

        def fetch(tbl, fill):
            full = jax.lax.all_gather(tbl, axes)      # (W, rows, ·)
            t = full.reshape(w * rows, tbl.shape[-1])[fi]
            return jnp.where(ok[:, None], t, fill)
    else:
        # all-to-all halo: req[s, i] = uniq[i] iff shard s owns it
        rank = _linear_rank(axes)
        my_lo = pg_l.part_offsets[rank]
        req = jnp.where(
            (own[None, :] == jnp.arange(w, dtype=jnp.int32)[:, None])
            & ok[None, :],
            uniq[None, :], -1,
        ).astype(jnp.int32)                           # (W, cap)
        got = jax.lax.all_to_all(req, axes, 0, 0)
        loc = got - my_lo
        inr = (got >= 0) & (loc >= 0) & (loc < rows)
        sl = jnp.clip(loc, 0, rows - 1)

        def fetch(tbl, fill):
            resp = jnp.where(inr[:, :, None], tbl[sl], fill)
            back = jax.lax.all_to_all(resp, axes, 0, 0)
            t = back[own, jnp.arange(cap)]
            return jnp.where(ok[:, None], t, fill)

    nbr_t = fetch(pg_l.nbr_sh[0], jnp.int32(-1))
    if mode == "edge":
        ned_t = fetch(pg_l.nbr_eid_sh[0], jnp.int32(-1))
        adj_t = jnp.zeros((cap, 1), jnp.uint32)
    else:
        adj_t = fetch(pg_l.adj_sh[0], jnp.uint32(0))
        ned_t = jnp.zeros((cap, 0), jnp.int32)
    return explore.TileView(
        uniq=uniq,
        labels=pg_l.labels,
        edge_uv=pg_l.edge_uv,
        edge_labels=pg_l.edge_labels,
        nbr_t=nbr_t,
        nbr_eid_t=ned_t,
        adj_t=adj_t,
    )


def make_sharded_expand_partitioned(app: MiningApp, mesh: Mesh,
                                    axes=("data",), halo: str = "alltoall",
                                    use_pallas: bool = False, interpret=None,
                                    compact_kernel: bool = False,
                                    with_patterns: bool = False):
    """The partitioned superstep (DESIGN.md §11): halo exchange + fused step.

    Each worker holds ONE CSR shard + adjacency tile of the graph
    (``PartitionedGraph``, in_specs split the shard-stacked tables over the
    mesh; vertex content stays replicated). Before expanding, the worker
    fetches its halo tile (:func:`halo_fetch_tile` — request/response
    ``all_to_all`` or the ragged all-gather fallback) and runs the SAME
    fused chunk program as every other backend. Both collectives live
    inside the one program, so the superstep keeps its single unclamped-
    count host sync — no new syncs appear.
    """

    mode = app.mode
    spec_in = P(axes)
    rep = P()

    @functools.partial(jax.jit, static_argnames=("out_cap",))
    def step(pg, members, n_valid, out_cap: int):
        w = pg.n_parts
        n = pg.n
        rows = pg.tile_rows

        def worker(pg_l, members, n_valid):
            m, nv = members[0], n_valid[0]
            with device_scope("halo_exchange"):
                view = halo_fetch_tile(
                    pg_l, m, nv,
                    mode=mode, halo=halo, axes=axes, w=w, rows=rows, n=n,
                    compact_kernel=compact_kernel, interpret=interpret,
                )
            with device_scope("fused_chunk"):
                children, count, codes, lv, ngen, ncanon = (
                    explore.fused_chunk_step(
                        view, m, nv, out_cap,
                        mode=mode,
                        app=app,
                        with_patterns=with_patterns,
                        use_pallas=use_pallas,
                        compact_kernel=compact_kernel,
                        interpret=interpret,
                    )
                )
            outs = (children[None], count[None], ngen[None], ncanon[None])
            if with_patterns:
                outs += (codes[None], lv[None])
            return outs

        pg_specs = PartitionedGraph(
            part_offsets=rep, labels=rep, edge_uv=rep, edge_labels=rep,
            nbr_sh=spec_in, nbr_eid_sh=spec_in, deg_sh=spec_in,
            adj_sh=spec_in,
        )
        n_out = 6 if with_patterns else 4
        return shard_map(
            worker,
            mesh=mesh,
            in_specs=(pg_specs, spec_in, spec_in),
            out_specs=(spec_in,) * n_out,
        )(pg, members, n_valid)

    return step


def make_sharded_halo_probe(mode: str, mesh: Mesh, axes=("data",),
                            halo: str = "alltoall",
                            compact_kernel: bool = False, interpret=None):
    """Standalone halo-fetch program for the ``trace_sync`` exchange probe
    (``StepStats.t_exchange``, DESIGN.md §12): the exact
    :func:`halo_fetch_tile` stage of the partitioned superstep, minus the
    fused chunk program, so its completion time is measurable without
    breaking the mining program's single-sync contract. Only dispatched
    while a ``sync=True`` tracer is installed."""
    spec_in = P(axes)
    rep = P()

    @jax.jit
    def probe(pg, members, n_valid):
        w, n, rows = pg.n_parts, pg.n, pg.tile_rows

        def worker(pg_l, members, n_valid):
            view = halo_fetch_tile(
                pg_l, members[0], n_valid[0],
                mode=mode, halo=halo, axes=axes, w=w, rows=rows, n=n,
                compact_kernel=compact_kernel, interpret=interpret,
            )
            return view.nbr_t[None]

        pg_specs = PartitionedGraph(
            part_offsets=rep, labels=rep, edge_uv=rep, edge_labels=rep,
            nbr_sh=spec_in, nbr_eid_sh=spec_in, deg_sh=spec_in,
            adj_sh=spec_in,
        )
        return shard_map(
            worker,
            mesh=mesh,
            in_specs=(pg_specs, spec_in, spec_in),
            out_specs=spec_in,
        )(pg, members, n_valid)

    return probe


class ShardCarried(NamedTuple):
    """Device-resident child pattern state carried between supersteps by
    the shard-map backend under ``device_aggregate`` (DESIGN.md §10): the
    per-worker quick codes / local-vertex tables stay in their padded
    (W, cap, ·) shard layout on device — replacing the post-hoc host
    concatenation the host path pays — plus the host-known valid counts."""

    codes: jnp.ndarray     # (W, cap, 3) int64
    lv: jnp.ndarray        # (W, cap, 8) int32
    counts: np.ndarray     # (W,) valid rows per worker


def _linear_rank(axes):
    """Worker rank linearised over the mesh axes (row-major in axis order,
    matching ``all_gather``'s concatenation order)."""
    r = jnp.int32(0)
    for a in axes:
        r = r * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return r


def make_sharded_quick_bin(mesh: Mesh, axes=("data",), use_kernel=False,
                           bin_method: str = "sort", interpret=None):
    """Device-resident level-1 aggregation over the mesh (DESIGN.md §10).

    Each worker bins its shard's quick codes locally
    (``kernels/aggregate.bin_rows``), all-gathers the O(Q)-sized distinct
    tables, deterministically re-bins the union into ONE global table
    (identical on every worker — the input is the gathered tables), and
    then **psums the per-slot counts** — the paper's Table-4 promise as a
    collective whose bytes scale with #patterns, never #embeddings. Also
    returns each row's global slot id (sharded, device-resident) for the
    FSM domain scatter and alpha row masks.

    Each worker bins at ``local_cap`` — a *pattern*-sized capacity, NOT the
    shard's row count — so the gathered tables are O(Q) on the wire. A
    worker whose distinct count overflows ``local_cap`` raises the
    all-reduced ``corrupt`` flag (riding the same drain as the distinct
    total, no extra sync) and the backend falls back to the host path for
    the step, growing the capacity for the next one.
    """
    spec = P(axes)

    @functools.partial(jax.jit, static_argnames=("local_cap", "global_cap"))
    def agg(codes_sh, valid_sh, local_cap: int, global_cap: int):
        def worker(codes, valid):
            codes, valid = codes[0], valid[0]
            # device-side §12 scope: the whole bin+gather+psum stage
            with device_scope("aggregate_bin"):
                u, c, inv, n, uv = agg_kernel_lib.bin_rows(
                    codes, valid, local_cap,
                    use_kernel=use_kernel, interpret=interpret,
                    method=bin_method,
                )
                gath_u = jax.lax.all_gather(u, axes)    # (W, cap, 3)
                gath_c = jax.lax.all_gather(c, axes)
                gath_v = jax.lax.all_gather(uv, axes)
                w = gath_u.shape[0]
                gu, _, ginv, gn, _ = agg_kernel_lib.bin_rows(
                    gath_u.reshape(w * local_cap, 3),
                    gath_v.reshape(w * local_cap),
                    global_cap,
                    use_kernel=use_kernel, interpret=interpret,
                    method=bin_method,
                )
                rank = _linear_rank(axes)
                my_map = jax.lax.dynamic_slice_in_dim(
                    ginv, rank * local_cap, local_cap
                )
                # THE collective: per-slot counts psum'd over the mesh
                # axes — bytes ∝ #patterns, not #embeddings (Table 4)
                seg = jnp.where(uv & (my_map >= 0), my_map, global_cap)
                local_counts = jnp.zeros(
                    (global_cap + 1,), jnp.int64
                ).at[seg].add(c)
                counts = jax.lax.psum(local_counts[:global_cap], axes)
                corrupt = jax.lax.pmax(
                    (n > local_cap).astype(jnp.int32), axes
                )
                row_slot = jnp.where(
                    inv >= 0, my_map[jnp.maximum(inv, 0)], -1
                ).astype(jnp.int32)
            return (gu[None], counts[None], gn[None], corrupt[None],
                    row_slot[None])

        return shard_map(
            worker,
            mesh=mesh,
            in_specs=(spec, spec),
            out_specs=(spec,) * 5,
        )(codes_sh, valid_sh)

    return agg


def make_sharded_domain_scatter(mesh: Mesh, axes=("data",)):
    """FSM phase 2 under ``device_aggregate``: every worker scatters its
    rows' vertices into the canonical domain bitmap at its global slots,
    then ONE OR(max)-allreduce merges the (pc_cap, 8, N) bitmaps — the
    paper's domain merge as a collective, with per-quick-slot level-2
    tables (q2c, sigma_inv) uploaded replicated."""
    spec = P(axes)
    rep = P()

    @functools.partial(jax.jit, static_argnames=("pc_cap", "n_vertices"))
    def scat(row_slot_sh, lv_sh, q2c, si, pc_cap: int, n_vertices: int):
        kmax = pattern_lib.MAX_PATTERN_VERTICES

        def worker(q2c, si, row_slot, lv):
            flat = jnp.zeros((pc_cap * kmax * n_vertices + 1,), dtype=bool)
            flat = aggregation.scatter_canon_bitmaps(
                flat, row_slot[0], lv[0], q2c, si, pc_cap, n_vertices
            )
            bm = flat[:-1].reshape(pc_cap, kmax, n_vertices)
            bm = jax.lax.pmax(bm.astype(jnp.int32), axes) > 0
            return bm[None]

        return shard_map(
            worker,
            mesh=mesh,
            in_specs=(rep, rep, spec, spec),
            out_specs=spec,
        )(q2c, si, row_slot_sh, lv_sh)

    return scat


def make_sharded_aggregate(mesh: Mesh, axes=("data",)):
    """Two-level aggregation's global reduce as ONE collective: counts psum +
    domain-bitmap OR(max)-allreduce over the mesh axes."""

    spec = P(axes)

    @functools.partial(jax.jit, static_argnames=("n_canon", "n_vertices"))
    def agg(canon_slot, verts_canon, valid, n_canon: int, n_vertices: int):
        def worker(canon_slot, verts_canon, valid):
            slot = canon_slot[0]
            counts = jax.ops.segment_sum(
                valid[0].astype(jnp.int64),
                jnp.where(valid[0], slot, n_canon),
                n_canon + 1,
            )[:n_canon]
            bitmaps = aggregation.domain_bitmaps(
                slot, verts_canon[0], valid[0], n_canon, n_vertices
            )
            # THE collective: bytes ∝ #patterns, not #embeddings (Table 4)
            counts = jax.lax.psum(counts, axes)
            bitmaps = jax.lax.pmax(bitmaps.astype(jnp.int32), axes) > 0
            return counts[None], bitmaps[None]

        counts, bitmaps = shard_map(
            worker,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=(spec, spec),
        )(canon_slot, verts_canon, valid)
        return counts[0], bitmaps[0]

    return agg


def place_partitioned(pg: PartitionedGraph, mesh: Mesh,
                      axes) -> PartitionedGraph:
    """Put each stacked shard table (``*_sh``, leading axis = shard axis)
    on its worker's device and replicate the rest over ``mesh``."""
    shard = NamedSharding(mesh, P(axes))
    rep = NamedSharding(mesh, P())
    return PartitionedGraph(*(
        jax.device_put(x, shard if f.endswith("_sh") else rep)
        for f, x in zip(pg._fields, pg)
    ))


class ShardMapBackend(ExecutionBackend):
    name = "shard_map"

    def __init__(self, mesh: Mesh, axes=None) -> None:
        self.mesh = auto_mesh(mesh)
        self._axes_override = axes

    def _make_store(self) -> FrontierStore:
        config, app = self.config, self.app
        self.axes = (
            self._axes_override if self._axes_override is not None
            else config.axes
        )
        self.n_shards = mesh_axis_size(self.mesh, self.axes)
        resolved_pallas = config.resolve_use_pallas()
        store = make_store(
            config.store, self.g,
            mode=app.mode,
            app_filter=programs.store_app_filter(app, self.g),
            use_pallas=resolved_pallas,
            interpret=config.pallas_interpret,
            dense_exchange=True,
        )
        # carried child codes need the next frontier to be exactly the
        # appended rows in order — raw store only (ODAG extraction
        # resurrects rows), and the naive-aggregation baseline deliberately
        # re-derives everything.
        self.with_patterns = (
            config.async_chunks
            and app.wants_patterns
            and store.kind == "raw"
            and not config.naive_aggregation
        )
        # device-resident level 1 (DESIGN.md §10): local bin + all-gathered
        # global table + per-slot psum/pmax; alpha must be pattern-granular
        self._device_agg = (
            config.device_aggregate
            and app.wants_patterns
            and not config.naive_aggregation
            and type(app).aggregation_filter is MiningApp.aggregation_filter
        )
        self._agg_kernel = config.resolve_aggregate_kernel()
        self._agg_bin = config.resolve_aggregate_bin()
        # level-2 placement (DESIGN.md §15): same contract as the serial
        # backend — host_async needs the deferrable device-agg path
        self._canon_placement = config.resolve_canonical_placement()
        if self._canon_placement == "host_async" and not (
            self._device_agg and aggregation.async_level2_ok(app)
        ):
            self._canon_placement = "host"
        if config.canonical_memo_cap is not None:
            pattern_lib.set_memo_cap(config.canonical_memo_cap)
        #: per-worker distinct-table capacity (pattern-sized, so gathered
        #: bytes stay O(Q)); grows pow2 after a host-fallback step
        self._shard_qcap = next_pow2(max(config.agg_qcap, 1))
        self._partitioned = isinstance(self.g, PartitionedGraph)
        if self._partitioned:
            if self.g.n_parts != self.n_shards:
                raise ValueError(
                    f"graph_partition={self.g.n_parts} must equal the "
                    f"shard-map worker count ({self.n_shards}): the "
                    f"in-program halo exchange maps one CSR shard per worker"
                )
            # one CSR shard + bitmap tile per worker device, the id/label
            # payload replicated: placed once, not resharded per program call
            self.g = place_partitioned(self.g, self.mesh, self.axes)
            self._halo = config.resolve_halo()
            self._expand = make_sharded_expand_partitioned(
                app, self.mesh, self.axes,
                halo=self._halo,
                use_pallas=resolved_pallas,
                interpret=config.pallas_interpret,
                compact_kernel=config.resolve_compact_kernel(),
                with_patterns=self.with_patterns,
            )
            self._halo_probe = make_sharded_halo_probe(
                app.mode, self.mesh, self.axes,
                halo=self._halo,
                compact_kernel=config.resolve_compact_kernel(),
                interpret=config.pallas_interpret,
            )
        else:
            self._expand = make_sharded_expand(
                app, self.mesh, self.axes,
                use_pallas=resolved_pallas,
                interpret=config.pallas_interpret,
                compact_kernel=config.resolve_compact_kernel(),
                with_patterns=self.with_patterns,
            )
        self._aggregate = make_sharded_aggregate(self.mesh, self.axes)
        self._quick_bin = make_sharded_quick_bin(
            self.mesh, self.axes,
            use_kernel=self._agg_kernel,
            bin_method=config.resolve_aggregate_bin(),
            interpret=config.pallas_interpret,
        )
        self._domain_scatter = make_sharded_domain_scatter(
            self.mesh, self.axes
        )
        return store

    # -- superstep hooks ----------------------------------------------------
    def begin_step(self, store, st) -> List[np.ndarray]:
        self._row_slot = None
        # raw: deterministic block split (broadcast-then-partition); odag:
        # §5.3 cost-annotated partitions, one extraction per worker.
        return store.worker_parts(self.n_shards)

    def quick_codes(self, blocks, size):
        frontier = (
            np.concatenate(blocks, axis=0)
            if any(len(p) for p in blocks)
            else np.zeros((0, size), np.int32)
        )
        b = len(frontier)
        qp = programs.quick_patterns(
            self.g, self.app.mode, jnp.asarray(frontier),
            jnp.full((b,), size, dtype=jnp.int32),
        )
        return np.asarray(qp.codes), np.asarray(qp.local_verts)

    def aggregate(self, codes, lv, st):
        g, app, config = self.g, self.app, self.config
        n_shards = self.n_shards
        b = len(codes)
        if config.naive_aggregation:
            # naive scheme: exchange per-EMBEDDING codes (an all-gather of
            # B x 24 bytes x workers) and run pattern canonicalisation once
            # per embedding instead of once per quick pattern.
            obs.count(st, "collective_bytes", int(codes.size * 8) * n_shards)
            for row in codes:
                pattern_lib.canonicalize_one(row)           # B iso checks
        uniq, inv = aggregation.quick_slot_ids(codes, np.ones(b, bool))
        # placement "device" routes the miss batch through the refine
        # kernel even on this host-reference path (bit-identical);
        # "host_async" has no deferrable table here and runs synchronously
        canon_fn = (
            canonical_refine.make_canon_fn(
                use_kernel=self._agg_kernel,
                interpret=config.pallas_interpret,
            )
            if self._canon_placement == "device"
            else None
        )
        table = pattern_lib.build_pattern_table(
            uniq, with_orbits=app.wants_domains, canon_fn=canon_fn
        )
        pc = len(table.canon_codes)
        canon_slot, verts_canon = aggregation.map_to_canonical_positions(
            table, inv, lv
        )
        # shard the level-1 inputs, reduce with the collective
        slot_sh, slot_counts = partition_frontier(canon_slot[:, None], n_shards)
        vc_sh, _ = partition_frontier(np.asarray(verts_canon), n_shards)
        per = slot_sh.shape[1]
        valid_sh = np.arange(per)[None, :] < slot_counts[:, None]
        counts, bitmaps = self._aggregate(
            jnp.asarray(slot_sh[:, :, 0]),
            jnp.asarray(vc_sh.reshape(n_shards, per, -1)),
            jnp.asarray(valid_sh),
            n_canon=max(pc, 1),
            n_vertices=g.n,
        )
        counts = np.asarray(counts[:pc])
        if app.wants_domains:
            supports = aggregation.min_image_support(
                bitmaps[:pc], table.canon_n_verts, table.canon_orbits
            )
        else:
            supports = counts.copy()
        agg_out = aggregation.StepAggregates(
            canon_codes=table.canon_codes,
            counts=counts.astype(np.int64),
            supports=np.asarray(supports).astype(np.int64),
            n_quick=len(uniq),
            n_canonical=pc,
            n_iso_checks=table.n_iso_checks,
        )
        obs.set_stat(st, "n_quick_patterns", agg_out.n_quick)
        obs.set_stat(st, "n_canonical_patterns", agg_out.n_canonical)
        obs.set_stat(
            st, "n_iso_checks",
            b if config.naive_aggregation else agg_out.n_iso_checks,
        )
        obs.count(
            st, "collective_bytes",
            counts.nbytes + (
                int(np.asarray(bitmaps[:pc]).size) // 8
                if app.wants_domains else 0
            ),
        )
        return agg_out, canon_slot

    # -- device-resident aggregation (DESIGN.md §10) ------------------------
    def aggregate_step(self, blocks, size, carried, st):
        if not self._device_agg:
            return super().aggregate_step(blocks, size, carried, st)
        g, app = self.g, self.app
        n_shards = self.n_shards
        n_frontier = sum(len(blk) for blk in blocks)
        if (
            isinstance(carried, ShardCarried)
            and int(carried.counts.sum()) == n_frontier
        ):
            # the children's codes never left the device (nor their padded
            # shard layout): aggregation is upload-free AND concat-free
            codes_sh, lv_sh, cnts = carried
            per = int(codes_sh.shape[1])
        else:
            padded, cnts = pad_parts(blocks, size)
            per = next_pow2(max(padded.shape[1], 1))
            if per > padded.shape[1]:
                padded = np.concatenate(
                    [padded,
                     np.full((n_shards, per - padded.shape[1], size),
                             -1, np.int32)],
                    axis=1,
                )
            nv = (
                (np.arange(per)[None, :] < cnts[:, None]) * size
            ).reshape(-1).astype(np.int32)
            qp = programs.quick_patterns(
                g, app.mode,
                jnp.asarray(padded.reshape(n_shards * per, size)),
                jnp.asarray(nv),
            )
            codes_sh = qp.codes.reshape(n_shards, per, 3)
            lv_sh = qp.local_verts.reshape(n_shards, per, -1)
        valid_sh = jnp.asarray(np.arange(per)[None, :] < cnts[:, None])
        local_cap = min(next_pow2(max(per, 1)), self._shard_qcap)
        global_cap = next_pow2(max(n_shards * local_cap, 1))
        gu, gcounts, gn, gcorrupt, row_slot = self._quick_bin(
            codes_sh, valid_sh, local_cap=local_cap, global_cap=global_cap
        )
        flags = np.asarray(jnp.stack([gn[0], gcorrupt[0].astype(gn.dtype)]))
        obs.count(st, "bytes_to_host", flags.nbytes)
        if faults_lib.take(
            self.config.faults, "aggregate", st.step, "saturate"
        ):
            # injected saturation: route through the host reference path
            # exactly as a tripped overflow flag would (DESIGN.md §13)
            flags = flags.copy()
            flags[1] = 1
        if int(flags[1]):
            # a worker's distinct table overflowed the pattern-sized cap:
            # host reference path for this step, bigger cap for the next
            codes, lv = self.quick_codes(blocks, size)
            obs.count(st, "bytes_to_host", codes.nbytes + lv.nbytes)
            agg_out, canon_slot = self.aggregate(codes, lv, st)
            self._shard_qcap = max(
                self._shard_qcap, next_pow2(max(agg_out.n_quick, 1))
            )
            return agg_out, canon_slot
        # the collective itself: gathered O(Q) tables + per-slot psum
        obs.count(
            st, "collective_bytes",
            n_shards * local_cap * (24 + 8 + 1) + global_cap * 8,
        )
        n = int(flags[0])
        # second tiny scalar read sizes the packed transfer (same packed
        # O(Q) drain as the serial backend's DeviceLevel1.finish)
        pflags = np.asarray(jnp.stack([
            jnp.any(gu[0][:n, 1] != 0),
            jnp.any(gu[0][:n, 2] != 0),
            jnp.max(gcounts[0][:n], initial=0) < jnp.int64(2) ** 31,
        ]))
        uniq, counts_q, tbytes = aggregation.drain_distinct(
            gu[0], gcounts[0], n,
            w1_used=bool(pflags[0]), w2_used=bool(pflags[1]),
            fit32=bool(pflags[2]),
        )
        obs.count(st, "bytes_to_host", pflags.nbytes + tbytes)
        placement = self._canon_placement
        if placement == "host_async":
            # overlap: joined by the loop at the seal boundary; eligibility
            # guarantees neither alpha_rows nor the domain scatter fires
            obs.annotate("canonicalize_submit")
            pending = aggregation.submit_level2(uniq, counts_q)
            self._row_slot, self._row_cnts = row_slot, cnts
            self._agg_table, self._agg_global_cap = None, global_cap
            return pending, None
        t0 = time.perf_counter()
        with obs.span("canonicalize", placement=placement, n_quick=n):
            if placement == "device" and n:
                # canonical re-bin runs on the REPLICATED global table
                # (identical on every worker post-gather): a second
                # non-collective program, so the superstep keeps its
                # <=2-sync contract — no new control reads appear
                uv_dev = jnp.arange(global_cap) < jnp.int32(n)
                table, counts, nbytes2 = aggregation.device_level2(
                    gu[0], gcounts[0], uv_dev, global_cap, n,
                    uniq, counts_q,
                    nvs=aggregation.level2_nvs(app, size),
                    with_domains=app.wants_domains,
                    use_kernel=self._agg_kernel,
                    interpret=self.config.pallas_interpret,
                    method=self._agg_bin,
                )
                obs.count(st, "bytes_to_host", nbytes2)
            else:
                table, counts = aggregation.finish_quick_level2(
                    uniq, counts_q, app.wants_domains
                )
        obs.count(st, "t_canon", time.perf_counter() - t0)
        pc = len(table.canon_codes)
        if app.wants_domains and pc:
            pc_cap = next_pow2(pc)
            q2c, si = aggregation.level2_device_tables(table, global_cap)
            bm_sh = self._domain_scatter(
                row_slot, lv_sh, q2c, si, pc_cap=pc_cap, n_vertices=g.n
            )
            obs.count(st, "collective_bytes", (pc_cap * 8 * g.n) // 8)
            bm = np.asarray(bm_sh[0][:pc])
            obs.count(st, "bytes_to_host", bm.nbytes)
            supports = aggregation.min_image_support(
                bm, table.canon_n_verts, table.canon_orbits
            )
        else:
            supports = counts.copy()
        agg_out = aggregation.build_step_aggregates(
            table, counts, supports, n, st
        )
        self._row_slot, self._row_cnts = row_slot, cnts
        self._agg_table, self._agg_global_cap = table, global_cap
        return agg_out, None

    def alpha_rows(self, pk, st):
        """Per-row alpha from the per-pattern verdict: one device gather
        through the sharded per-row global slot ids; only the bool mask
        crosses, re-assembled to sealed-frontier order via the per-worker
        valid counts."""
        table = self._agg_table
        q = len(table.quick_codes)
        pk_q = np.zeros(self._agg_global_cap, dtype=bool)
        pk_q[:q] = np.asarray(pk, dtype=bool)[table.quick_to_canon]
        slot = self._row_slot
        mask_sh = np.asarray(
            jnp.asarray(pk_q)[jnp.maximum(slot, 0)] & (slot >= 0)
        )
        obs.count(st, "bytes_to_host", mask_sh.nbytes)
        return np.concatenate(
            [mask_sh[s, : self._row_cnts[s]] for s in range(self.n_shards)]
        )

    def expand(self, store, blocks, size, st):
        # coordination-free sharded expansion over the (§5.3 cost-balanced)
        # per-worker slices
        g, n_shards = self.g, self.n_shards
        shards, counts_sh = pad_parts(blocks, size)
        per = shards.shape[1]
        n_valid = (np.arange(per)[None, :] < counts_sh[:, None]) * size
        members_dev = jnp.asarray(shards)
        n_valid_dev = jnp.asarray(n_valid.astype(np.int32))
        halo_bytes = (
            self._halo_bytes(per, size) if self._partitioned else 0
        )
        if self._partitioned:
            # the halo-exchange injection site (DESIGN.md §13): a planned
            # "halo" fault aborts here exactly where a lost worker would
            # surface; the supervisor's ladder answers with halo="gather"
            faults_lib.trip(self.config.faults, "halo", st.step)
        if self._partitioned and obs.sync_active():
            # trace_sync probe (DESIGN.md §12): the halo exchange runs
            # INSIDE the jitted superstep, so its share of t_expand is only
            # separable by re-running the fetch stage standalone — paid
            # exclusively in the diagnostic sync mode
            obs.count(
                st, "t_exchange",
                obs.probe_time(self._halo_probe, g, members_dev, n_valid_dev),
            )
        while True:
            outs = self._expand(g, members_dev, n_valid_dev,
                                out_cap=self.capacity)
            children, ccount = outs[0], outs[1]
            ccount = np.asarray(ccount)     # THE per-step control sync
            obs.count(st, "n_host_syncs", 1)
            obs.count(st, "n_chunks", 1)
            obs.count(st, "collective_bytes", halo_bytes)
            if int(ccount.max()) <= self.capacity:
                break
            # counts are exact (unclamped compaction), so exactly one
            # re-dispatch at the next pow2 bucket suffices
            programs.retire(*outs)
            self.capacity = next_pow2(int(ccount.max()))
        obs.set_stat(st, "n_generated", int(np.asarray(outs[2]).sum()))
        obs.set_stat(st, "n_canonical", int(np.asarray(outs[3]).sum()))

        # frontier exchange: worker-local children into the store as device
        # arrays (resolved at seal; odag: DenseODAG OR-allreduce, §5.2);
        # with the fused pipeline the children's pattern codes are carried
        # to the next superstep's aggregation
        for s in range(n_shards):
            store.append(children[s], worker=s, count=int(ccount[s]))
        if not self.with_patterns:
            return None
        if self._device_agg:
            # DESIGN.md §10: the child pattern state stays on device in its
            # shard layout — no post-hoc host concat, no host bytes
            return ShardCarried(
                codes=outs[4], lv=outs[5], counts=np.asarray(ccount)
            )
        codes_all = np.asarray(outs[4])
        lv_all = np.asarray(outs[5])
        return (
            np.concatenate(
                [codes_all[s, : ccount[s]] for s in range(n_shards)]
            ),
            np.concatenate(
                [lv_all[s, : ccount[s]] for s in range(n_shards)]
            ),
        )

    def _halo_bytes(self, per: int, size: int) -> int:
        """Per-dispatch halo-exchange wire bytes, computed host-side: the
        halo capacity is a static function of the chunk shape
        (``explore.halo_cap``) and row widths come from the shard tables,
        so the accounting needs no extra device output or sync."""
        g, mode, w = self.g, self.app.mode, self.n_shards
        cap = explore.halo_cap((per, size), mode, g.n)
        if mode == "edge":
            row = 2 * g.max_degree * 4          # nbr + edge-id rows, int32
        else:
            row = (g.max_degree + g.adj_sh.shape[2]) * 4
        if self._halo == "gather":
            # every worker all-gathers the full shard tables
            return w * w * g.tile_rows * row
        # request all-to-all (vertex ids) + response all-to-all (rows)
        return w * w * cap * (4 + row)

    def end_step(self, store, st) -> None:
        # frontier exchange: what a worker ships (raw rows, or the merged
        # ODAG with store="odag") rides the same collective accounting as
        # the aggregation reduce
        obs.count(st, "collective_bytes", store.exchange_bytes)
