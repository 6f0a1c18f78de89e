"""Pattern-keyed aggregation (paper §4.1 map/reduce + §5.4 two levels).

Level 1 runs on device over all embeddings of the step (counts, FSM domain
bitmaps keyed by *quick*-pattern slot). Level 2 maps quick slots to canonical
slots (host table from :mod:`repro.core.pattern`) and folds level-1 state —
the only stage that ever touches graph isomorphism.

Since DESIGN.md §10 level 1 is *device-resident* end to end:
:class:`DeviceLevel1` folds per-chunk / per-wave quick codes into a
device-side distinct table (``kernels/aggregate.py`` sort + segment-reduce),
and only O(Q) bytes — the distinct codes (packed uint32), their counts, and
the (Pc, 8, N) canonical domain bitmaps — ever cross to the host for level-2
canonicalisation. :func:`aggregate_rows` below is the host reference path
(``device_aggregate=False``), bit-identical by construction because both
paths emit distinct codes in ascending lexicographic order.

In the distributed runtime the level-1 state is exactly what gets
all-reduced: per-pattern scalars and domain bitmaps, never embeddings
(DESIGN.md §4) — this is how the paper's Table-4 reduction becomes a
collective-bytes reduction.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs
from repro.core import pattern as pattern_lib
from repro.kernels import aggregate as agg_kernel
from repro.kernels import canonical_refine


def _next_pow2(x: int) -> int:
    # lazy import: runtime.config (the canonical home of next_pow2) sits in
    # a package whose __init__ imports the loop, which imports this module
    from repro.core.runtime.config import next_pow2

    return next_pow2(x)


class StepAggregates(NamedTuple):
    """Aggregation output of one exploration step (canonical-pattern keyed)."""

    canon_codes: np.ndarray    # (Pc, 3) int64
    counts: np.ndarray         # (Pc,) int64 — #embeddings per pattern
    supports: np.ndarray       # (Pc,) int64 — min-image support (== counts
                               #   when domains were not requested)
    n_quick: int               # distinct quick patterns this step (Table 4)
    n_canonical: int           # distinct canonical patterns
    n_iso_checks: int          # graph-isomorphism invocations


def _unique_rows3(codes: np.ndarray):
    """``np.unique(axis=0, return_inverse=True)`` for (B, 3) int64 rows via
    a 3-key lexsort — ~5x faster than numpy's void-dtype row sort, which is
    the hottest host op of a superstep's aggregation (DESIGN.md §8)."""
    order = np.lexsort((codes[:, 2], codes[:, 1], codes[:, 0]))
    sc = codes[order]
    new = np.empty(len(sc), dtype=bool)
    new[0] = True
    np.any(sc[1:] != sc[:-1], axis=1, out=new[1:])
    uniq = sc[new]
    inv = np.empty(len(sc), dtype=np.int64)
    inv[order] = np.cumsum(new) - 1
    return uniq, inv


def quick_slot_ids(codes: jnp.ndarray, valid: jnp.ndarray):
    """Host-side unique over the (B, 3) quick codes -> (unique (Q,3), inv (B,)).

    The two-level scheme makes Q tiny (Table 4), so one host unique per step
    is cheap; rows with ``valid == False`` are mapped to slot -1.
    """
    codes_np = np.asarray(codes)
    valid_np = np.asarray(valid)
    if not valid_np.any():
        return np.zeros((0, 3), np.int64), np.full(len(codes_np), -1, np.int32)
    uniq, inv = _unique_rows3(codes_np[valid_np])
    full_inv = np.full(len(codes_np), -1, dtype=np.int32)
    full_inv[valid_np] = inv.astype(np.int32)
    return uniq, full_inv


@functools.partial(jax.jit, static_argnames=("n_canon", "n_vertices"))
def domain_bitmaps(
    canon_slot: jnp.ndarray,     # (B,) int32 canonical slot per embedding
    verts_canonical: jnp.ndarray,  # (B, 8) int32 graph vertex at canonical pos
    valid: jnp.ndarray,          # (B,) bool
    n_canon: int,
    n_vertices: int,
) -> jnp.ndarray:
    """FSM min-image domains (level-1): bool (Pc, 8, N) — vertex v appears at
    canonical position p of some embedding of pattern pc.

    One dense scatter; in the distributed engine this tensor is OR-allreduced
    (bool max) across workers — the paper's domain merge as one collective.
    """
    b, kmax = verts_canonical.shape
    flat = jnp.zeros((n_canon * kmax * n_vertices + 1,), dtype=bool)
    slot_ok = valid[:, None] & (verts_canonical >= 0) & (canon_slot[:, None] >= 0)
    idx = (
        canon_slot[:, None].astype(jnp.int64) * (kmax * n_vertices)
        + jnp.arange(kmax)[None, :] * n_vertices
        + jnp.maximum(verts_canonical, 0)
    )
    idx = jnp.where(slot_ok, idx, n_canon * kmax * n_vertices)
    flat = flat.at[idx.reshape(-1)].set(True)
    return flat[:-1].reshape(n_canon, kmax, n_vertices)


def min_image_support(
    bitmaps: jnp.ndarray, canon_n_verts: np.ndarray, canon_orbits: np.ndarray
) -> np.ndarray:
    """Support(p) = min over pattern positions of |domain(position)| [7].

    Domains are defined over *all* isomorphisms pattern->embedding; with one
    fixed isomorphism per embedding the missing mappings are recovered by
    OR-ing domains across each position's automorphism orbit
    (pattern.automorphism_orbits).
    """
    bm = np.asarray(bitmaps)                          # (Pc, 8, N) bool
    pc, kmax, n = bm.shape
    if pc == 0:
        return np.zeros((0,), np.int64)
    # orbit merge as one batched boolean matmul: eq[p, i, j] marks positions
    # in the same orbit, so (eq @ bm)[p, pos] > 0 ORs the orbit-mates'
    # domains — the (Pc x 8) Python double loop this replaces dominated
    # t_aggregate on labeled graphs (Pc large). uint8 is safe: row sums
    # are bounded by kmax = 8.
    orb = np.asarray(canon_orbits)[:, :kmax]
    eq = (orb[:, :, None] == orb[:, None, :]).astype(np.uint8)   # (Pc, 8, 8)
    merged = np.matmul(eq, bm.astype(np.uint8)) > 0              # (Pc, 8, N)
    counts = merged.sum(axis=2)                       # (Pc, 8)
    pos_ok = np.arange(kmax)[None, :] < np.asarray(canon_n_verts)[:, None]
    counts = np.where(pos_ok, counts, np.iinfo(np.int64).max)
    return counts.min(axis=1).astype(np.int64)


def map_to_canonical_positions(
    table: pattern_lib.PatternTable,
    quick_slot: np.ndarray,       # (B,) int32
    local_verts: jnp.ndarray,     # (B, 8) int32
) -> tuple[np.ndarray, jnp.ndarray]:
    """Per-embedding canonical slot + vertices re-ordered to canonical
    positions (position p holds local vertex with sigma[local]=p)."""
    sigma = table.sigma[np.maximum(quick_slot, 0)]    # (B, 8) local -> canon
    sigma_inv = np.argsort(sigma, axis=1)             # canon -> local
    lv = np.asarray(local_verts)
    verts_canon = np.take_along_axis(lv, sigma_inv, axis=1)
    canon_slot = np.where(
        quick_slot >= 0, table.quick_to_canon[np.maximum(quick_slot, 0)], -1
    ).astype(np.int32)
    return canon_slot, jnp.asarray(verts_canon)


def aggregate_rows(
    g_n_vertices: int,
    codes: np.ndarray,        # (B, 3) int64 quick codes (host)
    local_verts,              # (B, 8) int32 (host); None iff not with_domains
    with_domains: bool,
    canon_fn=None,            # level-2 miss hook (device placement)
) -> tuple[StepAggregates, np.ndarray]:
    """Full two-level aggregation for one step's embeddings, over
    pre-computed quick patterns (DESIGN.md §7).

    The engine computes quick patterns one device-budget wave at a time and
    merges the level-1 state here on the host (``bincount`` + boolean
    scatter), so aggregation never allocates a device array of frontier
    length — the frontier-store subsystem's device-budget contract. The
    distributed runtime keeps its own sharded level-1 path
    (:func:`make_sharded_aggregate` in :mod:`repro.core.runtime.shard`)
    whose reduce is the collective.

    Since DESIGN.md §10 this is the ``device_aggregate=False`` *reference*
    path: the default engines fold level 1 on device (:class:`DeviceLevel1`)
    and only O(Q) bytes cross to the host. Both paths emit distinct codes
    in ascending lexicographic order, so their outputs are bit-identical.

    Returns (aggregates, per-embedding canonical slot).
    """
    codes = np.asarray(codes)
    b = len(codes)
    uniq, inv = quick_slot_ids(codes, np.ones(b, dtype=bool))
    table = pattern_lib.build_pattern_table(
        uniq, with_orbits=with_domains, canon_fn=canon_fn
    )
    q = len(uniq)
    pc = len(table.canon_codes)
    if q == 0:
        empty = StepAggregates(
            canon_codes=np.zeros((0, 3), np.int64),
            counts=np.zeros((0,), np.int64),
            supports=np.zeros((0,), np.int64),
            n_quick=0,
            n_canonical=0,
            n_iso_checks=0,
        )
        return empty, np.full(b, -1, np.int32)

    quick_counts = np.bincount(inv, minlength=q).astype(np.int64)
    counts = np.zeros(pc, dtype=np.int64)
    np.add.at(counts, table.quick_to_canon, quick_counts)

    if with_domains:
        # domains need every embedding's vertices re-ordered to canonical
        # positions; without them the slot lookup is the whole mapping
        canon_slot, verts_canon = map_to_canonical_positions(
            table, inv, np.asarray(local_verts)
        )
        verts_canon = obs.to_host(verts_canon)
        kmax = verts_canon.shape[1]
        bm = np.zeros((pc, kmax, g_n_vertices), dtype=bool)
        ok = (verts_canon >= 0) & (canon_slot[:, None] >= 0)
        rows, pos = np.nonzero(ok)
        bm[canon_slot[rows], pos, verts_canon[rows, pos]] = True
        supports = min_image_support(bm, table.canon_n_verts, table.canon_orbits)
    else:
        canon_slot = table.quick_to_canon[inv].astype(np.int32)
        supports = counts.copy()

    agg = StepAggregates(
        canon_codes=table.canon_codes,
        counts=counts,
        supports=np.asarray(supports).astype(np.int64),
        n_quick=q,
        n_canonical=pc,
        n_iso_checks=table.n_iso_checks,
    )
    return agg, canon_slot


# ---------------------------------------------------------------------------
# Device-resident level 1 (DESIGN.md §10)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("cap", "use_kernel", "interpret", "method")
)
def _bin_all_valid(codes, cap: int, use_kernel: bool, interpret,
                   method: str = "sort"):
    """Bin one batch of all-valid quick codes at capacity ``cap``."""
    b = codes.shape[0]
    return agg_kernel.bin_rows(
        codes, jnp.ones((b,), bool), cap,
        use_kernel=use_kernel, interpret=interpret, method=method,
    )


@functools.partial(
    jax.jit, static_argnames=("cap", "use_kernel", "interpret", "method")
)
def _bin_weighted(codes, valid, weights, cap: int, use_kernel: bool,
                  interpret, method: str = "sort"):
    """Fold pre-binned partials: weighted re-bin of stacked unique tables."""
    return agg_kernel.bin_rows(
        codes, valid, cap, weights=weights,
        use_kernel=use_kernel, interpret=interpret, method=method,
    )


@jax.jit
def _finish_flags(uniq, counts, uvalid, n_stack, corrupt, sat):
    """The ONE scalar drain of a step's level-1 state: [final distinct
    count, max distinct count over every fold (merge-overflow detection),
    partial-corruption flag (a chunk's distinct count overflowed its bin
    capacity), w1/w2 column-used flags, counts-fit-int32 flag,
    count-saturation flag (a folded int32 partial hit the I32_SAT
    sentinel — totals would be floors, not counts)] — read together so
    overflow/saturation handling and the packed transfer cost no extra
    round trips."""
    w1_used = jnp.any(jnp.where(uvalid, uniq[:, 1], 0) != 0)
    w2_used = jnp.any(jnp.where(uvalid, uniq[:, 2], 0) != 0)
    fit32 = jnp.max(jnp.where(uvalid, counts, 0)) < jnp.int64(2) ** 31
    return jnp.stack(
        [n_stack[-1], jnp.max(n_stack), corrupt.astype(jnp.int32),
         w1_used.astype(jnp.int32), w2_used.astype(jnp.int32),
         fit32.astype(jnp.int32), sat.astype(jnp.int32)]
    ).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("flat_slots", "n_vertices"))
def _scatter_canon_flat(bm_flat, slot, lv, q2c, sigma_inv,
                        flat_slots: int, n_vertices: int):
    """Phase-2 FSM domain scatter (device): one batch of rows into the
    flat (pc_cap * 8 * N + 1) canonical-position bitmap (last slot = dump).

    ``slot`` is the per-row quick slot (final table order), ``q2c`` /
    ``sigma_inv`` the uploaded level-2 tables; vertex ``lv[r, sigma_inv[p]]``
    lands at canonical position ``p`` — the same re-ordering
    :func:`map_to_canonical_positions` applies on the host."""
    b, kmax = lv.shape
    safe = jnp.maximum(slot, 0)
    cs = jnp.where(slot >= 0, q2c[safe], -1)                       # (B,)
    vc = jnp.take_along_axis(lv, sigma_inv[safe], axis=1)          # (B, 8)
    ok = (vc >= 0) & (cs[:, None] >= 0)
    idx = (
        cs[:, None].astype(jnp.int64) * (kmax * n_vertices)
        + jnp.arange(kmax)[None, :] * n_vertices
        + jnp.maximum(vc, 0)
    )
    idx = jnp.where(ok, idx, flat_slots)
    return bm_flat.at[idx.reshape(-1)].set(True)


class DeviceLevel1:
    """Device-resident level-1 state of ONE superstep (DESIGN.md §10).

    Folds batches of quick codes — raw rows from a frontier wave
    (:meth:`fold_rows`) or pre-binned per-chunk partials emitted by the
    fused chunk programs (:meth:`fold_partial`) — into a device-side
    distinct table, without any host transfer. :meth:`finish` drains the
    O(Q) result: one (7,) scalar read, then the distinct codes packed to
    uint32 (label words dropped when unused) and the counts (int32 when
    they fit). Distinct codes come out in ascending lexicographic order,
    matching the host reference path bit for bit.

    Capacity discipline mirrors the chunk pipeline: per-batch bins use the
    batch's own pow2 capacity (can never overflow); cross-batch *merges*
    use ``merge_cap``, and an overflow — the unclamped distinct total rides
    the one scalar read — is re-merged at the exact pow2 capacity from the
    retained partials. Only when eager compaction (the stacked-drain fold,
    which merges pending chunk partials to bound device memory) has already
    dropped partials does :meth:`finish` return ``None``, and the caller
    re-folds from the frontier waves.

    Partial buffers are dropped (not eagerly deleted) once merged — they
    are O(cap) control state, not the O(step-output) children buffers the
    drain window retires.
    """

    def __init__(self, *, merge_cap: int, use_kernel: bool = False,
                 bin_method: str = "sort", interpret=None,
                 pending_limit: int = 32) -> None:
        self.merge_cap = int(merge_cap)
        self.rows = 0                   # host-known rows folded so far
        self.parts: List[tuple] = []    # (uniq, counts i64, uvalid, cap, n)
        self.batches: List[tuple] = []  # (inv, lv, part_idx)  [fold_rows]
        self._merge_ns: List = []       # device n of every cross-batch merge
        self._corrupt = None            # device flag: a partial overflowed
        self._sat = None                # device flag: int32 partial saturated
        self._compacted = False
        self._use_kernel = use_kernel
        self._bin_method = bin_method
        self._interpret = interpret
        self._pending_limit = pending_limit
        self._final = None              # (uniq, counts, uvalid, cap, n)
        self._maps: Optional[List] = None

    # -- folding ------------------------------------------------------------
    def fold_rows(self, codes, lv=None) -> None:
        """Fold one wave's (B, 3) quick codes (all rows valid); ``lv``
        (device) is retained for the FSM phase-2 domain scatter."""
        b = int(codes.shape[0])
        if b == 0:
            return
        cap = _next_pow2(b)
        u, c, inv, n, uv = _bin_all_valid(
            codes, cap, self._use_kernel, self._interpret, self._bin_method
        )
        self.parts.append((u, c, uv, cap, n))
        self.batches.append((inv, lv, len(self.parts) - 1))
        self.rows += b

    def fold_partial(self, uniq, counts, n, cap: int, rows: int,
                     may_overflow: bool = False) -> None:
        """Fold one chunk program's pre-binned partial: ``uniq`` (cap, 3),
        ``counts`` (cap,) and the device distinct count ``n`` (unclamped).
        ``may_overflow`` marks partials binned below the chunk's child
        capacity (``agg_qcap``-bounded): ``n > cap`` then means the dump
        slot swallowed patterns — tracked as a device flag that rides the
        finish drain, after which the caller re-folds from the waves."""
        uv = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(n, cap)
        if counts.dtype == jnp.int32:
            # a narrowed partial (the fused chunk programs emit int32):
            # the I32_SAT sentinel means the true count was clipped — a
            # device flag rides the finish drain, after which the caller
            # re-folds the step from the waves in int64 (DESIGN.md §13)
            hit = jnp.any(
                jnp.where(uv, counts, 0) >= jnp.int32(agg_kernel.I32_SAT)
            )
            self._sat = hit if self._sat is None else (self._sat | hit)
        self.parts.append((uniq, counts.astype(jnp.int64), uv, cap, n))
        self.rows += rows
        if may_overflow:
            bad = n > cap
            self._corrupt = bad if self._corrupt is None else (
                self._corrupt | bad
            )
        if len(self.parts) >= self._pending_limit:
            self._compact()

    def _merge(self, parts, cap: int):
        u = jnp.concatenate([p[0] for p in parts])
        c = jnp.concatenate([p[1] for p in parts])
        v = jnp.concatenate([p[2] for p in parts])
        mu, mc, minv, mn, muv = _bin_weighted(
            u, v, c, cap, self._use_kernel, self._interpret, self._bin_method
        )
        self._merge_ns.append(mn)
        return mu, mc, minv, mn, muv

    def _compact(self) -> None:
        mu, mc, _, mn, muv = self._merge(self.parts, self.merge_cap)
        self.parts = [(mu, mc, muv, self.merge_cap, mn)]
        self._compacted = True

    # -- the O(Q) drain -----------------------------------------------------
    def _finalize(self, cap: int):
        if len(self.parts) == 1:
            # a lone batch bin (cap >= rows) or an eager compaction: never
            # re-merged — overflow of the latter is caught via _merge_ns
            u, c, uv, pcap, n = self.parts[0]
            self._maps = [None]
            return u, c, uv, pcap, n
        mu, mc, minv, mn, muv = self._merge(self.parts, cap)
        off, maps = 0, []
        for p in self.parts:
            maps.append(jax.lax.slice_in_dim(minv, off, off + p[3]))
            off += p[3]
        self._maps = maps
        return mu, mc, muv, cap, mn

    def finish(self):
        """Drain the folded state to the host: ``(uniq (Q, 3) int64,
        counts (Q,) int64, bytes_to_host)`` — or ``None`` when an eager
        compaction overflowed ``merge_cap`` (state unrecoverable on device;
        re-fold from the frontier waves). ``observed_n`` afterwards holds
        the true distinct total, so the re-fold can size itself exactly."""
        if not self.parts:
            self.observed_n = 0
            return np.zeros((0, 3), np.int64), np.zeros((0,), np.int64), 0
        u, c, uv, cap, n = self._finalize(self.merge_cap)
        corrupt = (
            self._corrupt if self._corrupt is not None else jnp.zeros((), bool)
        )
        sat = self._sat if self._sat is not None else jnp.zeros((), bool)
        stack = jnp.stack([jnp.asarray(x, jnp.int32) for x in
                           (self._merge_ns + [n])])
        flags = obs.to_host(_finish_flags(u, c, uv, stack, corrupt, sat))
        nbytes = flags.nbytes
        self.observed_n = n_final = int(flags[0])
        max_n = int(flags[1])
        if flags[2]:
            return None             # a chunk partial overflowed its bin
        if flags[6]:
            # an int32 partial saturated at I32_SAT: its totals are floors;
            # the wave re-fold re-bins everything in int64 (DESIGN.md §13)
            return None
        if max_n > cap:
            if self._compacted:
                return None
            # exact re-merge from the retained partials: the unclamped
            # distinct total rode the scalar read, no extra sync
            u, c, uv, cap, n = self._finalize(_next_pow2(max_n))
            stack = jnp.stack([jnp.asarray(self._merge_ns[-1], jnp.int32)])
            flags = obs.to_host(
                _finish_flags(u, c, uv, stack, jnp.zeros((), bool),
                              jnp.zeros((), bool))
            )
            nbytes += flags.nbytes
            self.observed_n = n_final = int(flags[0])
        # packed transfer: only used code words cross, counts narrowed
        uniq, counts, tbytes = drain_distinct(
            u, c, n_final,
            w1_used=bool(flags[3]), w2_used=bool(flags[4]),
            fit32=bool(flags[5]),
        )
        self._final = (u, c, uv, cap, n)
        return uniq, counts, nbytes + tbytes

    # -- per-row slots (alpha masks, FSM phase 2) ---------------------------
    def batch_slots(self, i: int):
        """Device per-row slot ids of batch ``i`` in FINAL table order."""
        inv, _, pidx = self.batches[i]
        m = self._maps[pidx] if self._maps is not None else None
        return m[inv] if m is not None else inv

    @property
    def final_cap(self) -> int:
        return self._final[3] if self._final is not None else self.merge_cap


def drain_distinct(u_dev, c_dev, n: int, w1_used: bool, w2_used: bool,
                   fit32: bool):
    """The packed O(Q) device→host drain both backends share: distinct
    codes as uint32 with unused label words dropped (lossless by the
    encoding), counts narrowed to int32 when they fit. Returns
    ``(uniq (n, 3) int64, counts (n,) int64, bytes_transferred)``."""
    cols = [0] + ([1] if w1_used else []) + ([2] if w2_used else [])
    packed = obs.to_host(
        agg_kernel.pack_codes_u32(u_dev[:n][:, jnp.asarray(cols)])
    )
    uniq = np.zeros((n, 3), np.int64)
    uniq[:, cols] = agg_kernel.unpack_codes_u32(packed)
    cdev = c_dev[:n]
    counts = obs.to_host(cdev.astype(jnp.int32) if fit32 else cdev)
    return uniq, counts.astype(np.int64), packed.nbytes + counts.nbytes


def build_step_aggregates(table: pattern_lib.PatternTable,
                          counts: np.ndarray, supports, n_quick: int,
                          st) -> StepAggregates:
    """Assemble a step's :class:`StepAggregates` from level-2 output and
    mirror the pattern counters into the step stats — shared by both
    backends' device-aggregation paths so the two can never drift."""
    agg = StepAggregates(
        canon_codes=table.canon_codes,
        counts=counts,
        supports=np.asarray(supports).astype(np.int64),
        n_quick=n_quick,
        n_canonical=len(table.canon_codes),
        n_iso_checks=table.n_iso_checks,
    )
    obs.set_stat(st, "n_quick_patterns", agg.n_quick)
    obs.set_stat(st, "n_canonical_patterns", agg.n_canonical)
    obs.set_stat(st, "n_iso_checks", agg.n_iso_checks)
    return agg


def finish_quick_level2(uniq: np.ndarray, counts_q: np.ndarray,
                        with_domains: bool, canon_fn=None):
    """Host level 2 over device-drained level-1 state: canonicalise the Q
    distinct quick codes (memoised, :func:`pattern.build_pattern_table`)
    and fold the quick counts to canonical slots. Returns
    ``(table, counts (Pc,) int64)``."""
    table = pattern_lib.build_pattern_table(
        uniq, with_orbits=with_domains, canon_fn=canon_fn
    )
    pc = len(table.canon_codes)
    counts = np.zeros(pc, dtype=np.int64)
    np.add.at(counts, table.quick_to_canon, counts_q.astype(np.int64))
    return table, counts


# ---------------------------------------------------------------------------
# Level-2 placement (DESIGN.md §15): device re-bin + async host overlap
# ---------------------------------------------------------------------------

def async_level2_ok(app) -> bool:
    """True when level 2 may run off the critical path (``host_async``).

    The deferred table must not be consulted mid-step: apps that override
    ``pattern_filter`` (FSM's support prune feeds alpha) or the per-row
    ``aggregation_filter``, or that consume orbit domains, need the table
    before expansion — they silently run the synchronous host placement
    instead (bit-identical output either way)."""
    from repro.core.api import MiningApp

    return (
        app.wants_patterns
        and not app.wants_domains
        and type(app).pattern_filter is MiningApp.pattern_filter
        and type(app).aggregation_filter is MiningApp.aggregation_filter
    )


_ASYNC_EXECUTOR = None


def _async_executor():
    global _ASYNC_EXECUTOR
    if _ASYNC_EXECUTOR is None:
        from concurrent.futures import ThreadPoolExecutor

        # one worker: supersteps submit at most one level-2 batch each and
        # join it at the next seal, so deeper parallelism buys nothing and
        # single-worker FIFO keeps memo writes ordered.
        _ASYNC_EXECUTOR = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="repro-canon"
        )
    return _ASYNC_EXECUTOR


class PendingLevel2:
    """An in-flight ``host_async`` level-2 batch: the backend submits the
    drained O(Q) table to the background thread and the loop joins the
    future at the seal boundary — canonicalisation overlaps the next
    superstep's expansion instead of sitting on the critical path."""

    def __init__(self, future, n_quick: int):
        self._future = future
        self.n_quick = n_quick

    def done(self) -> bool:
        return self._future.done()

    def result(self):
        """Block until the batch lands: ``(table, counts (Pc,) int64)``."""
        return self._future.result()


def submit_level2(uniq: np.ndarray, counts_q: np.ndarray) -> PendingLevel2:
    """Queue one step's host level 2 on the background thread (domains are
    never requested here — ``async_level2_ok`` excludes domain apps)."""
    fut = _async_executor().submit(finish_quick_level2, uniq, counts_q, False)
    return PendingLevel2(fut, len(uniq))


@functools.partial(
    jax.jit,
    static_argnames=("cap", "nvs", "with_orbits", "use_kernel", "interpret",
                     "method"),
)
def _level2_program(u, c, uv, cap: int, nvs: tuple, with_orbits: bool,
                    use_kernel: bool, interpret, method: str):
    """The in-program device level 2: batched canonical refine of the
    O(Q) distinct table + weighted quick→canonical re-bin (+ the orbit
    pass over the canonical table for FSM). ``bin_rows`` emits distinct
    codes in ascending lexicographic order — the same order as the host's
    ``np.unique`` — so every output is bit-identical to the host path."""
    canon, sigma, _ = canonical_refine.refine_codes(
        u, uv, nvs, with_orbits=False, use_kernel=use_kernel,
        interpret=interpret,
    )
    canon = jnp.where(uv[:, None], canon, 0)
    cu, cc, q2c, cn, cuv = agg_kernel.bin_rows(
        canon, uv, cap, weights=c,
        use_kernel=use_kernel, interpret=interpret, method=method,
    )
    if with_orbits:
        _, _, rep = canonical_refine.refine_codes(
            cu, cuv, nvs, with_orbits=True, use_kernel=use_kernel,
            interpret=interpret,
        )
    else:
        rep = jnp.tile(jnp.arange(8, dtype=jnp.int32), (cap, 1))
    return canon, sigma, cu, cc, q2c, cn, cuv, rep


def device_level2(u, c, uv, cap: int, n_final: int, quick_codes: np.ndarray,
                  counts_q: np.ndarray, *, nvs: tuple, with_domains: bool,
                  use_kernel: bool = False, interpret=None,
                  method: str = "sort"):
    """Device-placed level 2 over the finalized device level-1 state.

    ``u``/``c``/``uv`` are the device distinct table (capacity ``cap``),
    ``n_final`` the already-drained distinct count, ``quick_codes`` /
    ``counts_q`` the host copies from the level-1 drain (the quick table
    still crosses — phase 2 and the memo need it; what this path removes
    is the host permutation search). The canonical table can never
    overflow ``cap`` (Pc ≤ Q ≤ cap), so no growth rung is needed.

    Returns ``(table, counts (Pc,) int64, bytes_to_host)``.
    """
    canon_d, sigma_d, cu_d, cc_d, q2c_d, cn_d, cuv_d, rep_d = _level2_program(
        u, c, uv, cap, nvs, with_domains, use_kernel, interpret, method
    )
    q = int(n_final)
    pc = int(obs.to_host(cn_d))
    sigma = obs.to_host(sigma_d[:q], np.int32)
    q2c = obs.to_host(q2c_d[:q], np.int32)
    cu = obs.to_host(cu_d[:pc], np.int64)
    cc = obs.to_host(cc_d[:pc], np.int64)
    canon_rows = obs.to_host(canon_d[:q], np.int64)
    if with_domains:
        orbits = obs.to_host(rep_d[:pc], np.int32)
    else:
        orbits = np.tile(
            np.arange(pattern_lib.MAX_PATTERN_VERTICES, dtype=np.int32),
            (pc, 1),
        )
    nbytes = (sigma.nbytes + q2c.nbytes + cu.nbytes + cc.nbytes
              + canon_rows.nbytes + (orbits.nbytes if with_domains else 0) + 4)
    table = pattern_lib.PatternTable(
        quick_codes=quick_codes,
        canon_codes=cu,
        quick_to_canon=q2c,
        sigma=sigma,
        canon_n_verts=(cu[:, 0] & 0xF).astype(np.int32),
        canon_orbits=orbits,
        n_iso_checks=q,
    )
    # warm the host memo with the device results: a later host placement
    # (degradation rung, resumed run) over the same patterns is then pure
    # cache hits.
    pattern_lib.seed_memo(
        quick_codes, canon_rows, sigma,
        canon_codes=cu if with_domains else None,
        orbits=orbits if with_domains else None,
    )
    return table, cc, nbytes


def level2_nvs(app, size: int) -> tuple:
    """STATIC nv set of the patterns a step of ``size`` may emit: the
    per-nv refine passes of the device placement are compiled per this
    tuple. Vertex mode explores fixed-size embeddings (nv == size); edge
    mode's embeddings of k edges span 2..min(k+1, 8) vertices."""
    if getattr(app, "mode", "vertex") == "edge":
        # a connected k-edge embedding spans 2..k+1 vertices (tree upper
        # bound), capped at the 8-vertex encoding limit
        hi = min(int(size) + 1, pattern_lib.MAX_PATTERN_VERTICES)
        return tuple(range(2, hi + 1))
    return (min(int(size), pattern_lib.MAX_PATTERN_VERTICES),)


def level2_device_tables(table: pattern_lib.PatternTable, cap: int):
    """Upload the level-2 mapping for device phase-2 consumers (domain
    scatter, alpha-mask gathers): ``q2c`` (cap,) int32 padded with -1 and
    ``sigma_inv`` (cap, 8) int32 (canonical pos -> local pos)."""
    q = len(table.quick_codes)
    q2c = np.full(cap, -1, np.int32)
    q2c[:q] = table.quick_to_canon
    si = np.zeros((cap, pattern_lib.MAX_PATTERN_VERTICES), np.int32)
    si[:q] = np.argsort(table.sigma, axis=1)
    return jnp.asarray(q2c), jnp.asarray(si)


def scatter_canon_bitmaps(bm_flat, slot, lv, q2c, sigma_inv,
                          pc_cap: int, n_vertices: int):
    """Accumulate one batch into the flat canonical domain bitmap (see
    :func:`_scatter_canon_flat`); ``bm_flat`` is the
    (pc_cap * 8 * N + 1,) bool accumulator threaded across batches."""
    return _scatter_canon_flat(
        bm_flat, slot, lv, q2c, sigma_inv,
        pc_cap * pattern_lib.MAX_PATTERN_VERTICES * n_vertices, n_vertices,
    )


