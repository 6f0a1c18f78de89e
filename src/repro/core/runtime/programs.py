"""Shared device-program machinery of the superstep runtime (DESIGN.md §8/§9).

Everything both execution backends need around ``explore.fused_chunk_step``:
the process-wide jitted chunk-program cache, device-side chunk slicing,
quick-pattern dispatch, eager buffer retirement, and the store-facing app
filter adapter. Extracted from the old ``core/engine.py`` so the serial and
shard-map backends build on one copy.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import explore, pattern as pattern_lib
from repro.core.api import MiningApp
from repro.core.graph import DeviceGraph
from repro.core.runtime.config import next_pow2

#: process-wide jitted chunk programs, keyed by (app identity, flags).
#: Re-running an engine with an equivalent app config reuses the compiled
#: programs instead of re-tracing per run — the jit cache is what the pow2
#: bucketing bounds (DESIGN.md §8), so it should be shared, not rebuilt.
_CHUNK_PROGRAM_CACHE: Dict[tuple, object] = {}


def app_cache_key(app: MiningApp):
    """Hashable identity of an app's *traced* behaviour (class + dataclass
    fields), or None when the app carries unhashable state."""
    try:
        fields = tuple(
            (f.name, getattr(app, f.name)) for f in dataclasses.fields(app)
        )
        key = (type(app).__module__, type(app).__qualname__, fields)
        hash(key)
        return key
    except (TypeError, ValueError):
        return None


def make_expand_fn(app: MiningApp, mode: str, use_pallas: bool = False,
                   fused: bool = False, interpret=None,
                   compact_kernel: bool = False, with_patterns: bool = False,
                   with_aggregates: bool = False, agg_qcap: int = 4096,
                   aggregate_kernel: bool = False, aggregate_bin: str = "sort",
                   with_local_verts: bool = True):
    """Jitted chunk program of the superstep pipeline: expand + canonicality
    + app filter + compaction (+ child quick patterns when the pipeline is
    fused, or the binned per-chunk level-1 partial with ``with_aggregates``
    — DESIGN.md §10). Recompiled per (width, capacity) pow2 bucket, and per
    lane bucket ``n_lanes`` on the packed path (:func:`plan_chunks`);
    cached across runs for hashable app configs."""
    app_key = app_cache_key(app)
    key = None
    if app_key is not None:
        key = (app_key, mode, use_pallas, fused, interpret,
               compact_kernel, with_patterns, with_aggregates, agg_qcap,
               aggregate_kernel, aggregate_bin, with_local_verts)
        cached = _CHUNK_PROGRAM_CACHE.get(key)
        if cached is not None:
            return cached

    @functools.partial(jax.jit, static_argnames=("out_cap", "n_lanes"))
    def fn(g: DeviceGraph, members, n_valid, out_cap: int, n_lanes=None):
        return explore.fused_chunk_step(
            g, members, n_valid, out_cap,
            mode=mode,
            app=app,
            with_patterns=with_patterns,
            with_aggregates=with_aggregates,
            agg_qcap=agg_qcap,
            with_local_verts=with_local_verts,
            use_pallas=use_pallas,
            fused=fused,
            compact_kernel=compact_kernel,
            aggregate_kernel=aggregate_kernel,
            aggregate_bin=aggregate_bin,
            n_lanes=n_lanes,
            interpret=interpret,
        )

    if key is not None:
        _CHUNK_PROGRAM_CACHE[key] = fn
    return fn


def jit_cache_size(fn) -> Optional[int]:
    try:
        return int(fn._cache_size())
    except Exception:  # pragma: no cover - older/newer jax internals
        return None


def initial_frontier(g: DeviceGraph, mode: str) -> np.ndarray:
    """Superstep-1 frontier: every vertex (vertex mode) or edge (edge mode)."""
    n0 = g.n if mode == "vertex" else g.m
    return np.arange(n0, dtype=np.int32)[:, None]


@functools.partial(jax.jit, static_argnames=("mode",))
def _quick_patterns(g, members, n_valid, mode: str):
    if mode == "vertex":
        return pattern_lib.quick_pattern_vertex(g, members, n_valid)
    return pattern_lib.quick_pattern_edge(g, members, n_valid)


def quick_patterns(g: DeviceGraph, mode: str, members, n_valid):
    """Quick-pattern codes of a frontier batch, as one jitted program per
    batch shape (run eagerly, every primitive would compile on its own for
    each new wave size)."""
    return _quick_patterns(g, members, n_valid, mode)


def device_chunk(wave_dev, lo: int, cb: int, bucket: int, k: int):
    """Slice chunk ``[lo, lo+cb)`` out of a device-resident wave and pad it
    to its pow2 ``bucket`` on device — no host round-trip per chunk (the
    PR-2 loop re-built every chunk from the host wave)."""
    chunk = jax.lax.slice_in_dim(wave_dev, lo, lo + cb)
    n_valid = jnp.full((cb,), k, jnp.int32)
    if bucket > cb:
        chunk = jnp.concatenate(
            [chunk, jnp.full((bucket - cb, k), -1, jnp.int32)]
        )
        n_valid = jnp.concatenate(
            [n_valid, jnp.zeros((bucket - cb,), jnp.int32)]
        )
    return chunk, n_valid


def retire(*buffers) -> None:
    """Best-effort immediate deletion of drained device buffers (instead of
    waiting for GC) — the fused pipeline's peak-HBM control."""
    for b in buffers:
        if hasattr(b, "delete"):
            try:
                b.delete()
            except Exception:
                pass


class ChunkPlan(NamedTuple):
    """One chunk of a wave: rows ``[lo, lo + rows)`` padded to ``bucket``
    rows. ``lanes`` is the candidate lanes the chunk program computes and
    ``used`` how many of them hold a member's neighbour slot; ``packed``
    says whether the program takes ``lanes`` as its packed lane bucket."""

    lo: int
    rows: int
    bucket: int
    lanes: int
    used: int
    packed: bool


def plan_chunks(wave: np.ndarray, chunk_size: int, member_lanes: int,
                deg: Optional[np.ndarray] = None):
    """Cut a host wave of ``(N, k)`` rows into consecutive chunks.
    ``member_lanes`` is the padded lanes of one member: D in vertex mode,
    2·D in edge mode (two endpoint slots).

    Padded (``deg`` None): ``chunk_size`` rows each, padded to a pow2 row
    bucket, ``bucket * k * member_lanes`` lanes. Packed (``deg``, the host
    degree array, vertex mode, DESIGN.md §8): a row needs
    ``sum(deg(members))`` lanes, and a chunk takes rows in order while
    they fit the lane budget ``chunk_size * k * D`` — a full padded
    chunk's lanes — so the budget, not ``chunk_size``, sets its rows. Rows
    and lanes are padded to pow2 buckets (the lanes capped at the budget),
    so a size compiles O(log^2) programs. A row costs at least one lane of
    the budget, which bounds a chunk's rows too."""
    n, k = wave.shape
    if deg is None:
        lanes_per_row = k * member_lanes
        for lo in range(0, n, chunk_size):
            cb = min(chunk_size, n - lo)
            bucket = min(chunk_size, next_pow2(max(cb, 1)))
            yield ChunkPlan(lo, cb, bucket, bucket * lanes_per_row,
                            cb * lanes_per_row, False)
        return
    budget = chunk_size * k * member_lanes
    need = deg[wave].sum(axis=1, dtype=np.int64)
    ends = np.cumsum(np.maximum(need, 1))
    lanes = np.cumsum(need)
    lo = 0
    while lo < n:
        base = ends[lo - 1] if lo else 0
        hi = int(np.searchsorted(ends, base + budget, side="right"))
        used = int(lanes[hi - 1] - (lanes[lo - 1] if lo else 0))
        yield ChunkPlan(lo, hi - lo, next_pow2(hi - lo),
                        min(budget, next_pow2(max(used, 1))), used, True)
        lo = hi


def iter_chunks(waves, wave_dev, size: int, plan):
    """Yield ``(wave index, ChunkPlan, chunk, n_valid)`` over all waves:
    each wave cut by ``plan`` (host wave -> its chunk plans),
    uploaded at most once (reusing the aggregation pass's upload), and
    sliced and padded on device."""
    for wi, w in enumerate(waves):
        if not len(w):
            continue
        if wave_dev[wi] is None:
            wave_dev[wi] = jnp.asarray(np.ascontiguousarray(w))
        wd = wave_dev[wi]
        for p in plan(w):
            chunk, n_valid = device_chunk(wd, p.lo, p.rows, p.bucket, size)
            yield wi, p, chunk, n_valid


def store_app_filter(app: MiningApp, g: DeviceGraph):
    """Adapt ``app.filter`` to the per-candidate signature ODAG extraction
    re-applies (DESIGN.md §7): extraction rows are already one member-set per
    candidate, so the parent-row indirection is the identity. Returns None
    for the base accept-all filter (nothing to re-apply)."""
    if type(app).filter is MiningApp.filter:
        return None

    @jax.jit
    def filt(g, mem, nv, cnd):
        rows = jnp.arange(int(mem.shape[0]), dtype=jnp.int32)
        return app.filter(g, mem, nv, rows, cnd)

    def phi(mem, nv, cnd):
        return filt(g, mem, nv, cnd)

    return phi
