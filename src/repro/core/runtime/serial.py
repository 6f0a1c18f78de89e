"""Serial execution backend: the single-device fused superstep pipeline.

Wraps the chunk-program dataflow of DESIGN.md §8 behind the
:class:`~repro.core.runtime.backend.ExecutionBackend` protocol: the sealed
frontier re-materialises in device-budget waves, each wave is uploaded
once and sliced into pow2-padded chunks on device, a *pilot* chunk
calibrates the step's output-capacity bucket, the remaining chunks dispatch
back-to-back with counts left on device, and the host drains all control
values in stacked window reads — at most TWO host syncs per superstep
(``async_chunks=True``). The PR-2 chunk loop (one blocking ``int(count)``
per chunk) is preserved bit-for-bit as ``async_chunks=False``, the
benchmark baseline of ``benchmarks/bench_superstep.py``.

Pattern aggregation is device-resident by default (DESIGN.md §10,
``device_aggregate=True``): chunk programs emit pre-binned level-1
*partials* that fold across the stacked-drain window
(:class:`repro.core.aggregation.DeviceLevel1`), or — when the store cannot
carry (ODAG resurrection) or FSM needs the local-vertex table — the waves
are re-binned on device at aggregation time. Either way only O(Q) bytes
(distinct codes, counts, canonical domain bitmaps, and an alpha row mask
iff pruning fires) ever cross to the host; ``device_aggregate=False`` keeps
the host reference path (``aggregation.aggregate_rows``).
"""
from __future__ import annotations

import functools
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, explore, obs, pattern as pattern_lib
from repro.kernels import canonical_refine
from repro.core.api import MiningApp
from repro.core.graph import PartitionedGraph
from repro.core.runtime import faults as faults_lib
from repro.core.runtime import programs
from repro.core.runtime.backend import ExecutionBackend
from repro.core.runtime.config import next_pow2
from repro.core.store import FrontierStore, make_store

#: chunk programs in flight between drains: bounds how many capacity-
#: padded output buffers are device-resident at once (peak HBM is
#: O(window * step_cap), not O(step output)) while keeping host syncs at
#: O(chunks / window) per superstep — 1 + pilot for any step under ~32
#: chunks.
_DRAIN_WINDOW = 32


def _charges_reads(hook):
    """Charge the blocking device reads (``obs.to_host``) a backend hook
    makes to ``st.n_device_reads`` (``st``: the hook's last argument):
    a host-side tally, no device work."""
    @functools.wraps(hook)
    def charged(self, *args):
        r0 = obs.read_count()
        try:
            return hook(self, *args)
        finally:
            obs.count(args[-1], "n_device_reads", obs.read_count() - r0)
    return charged


class SerialBackend(ExecutionBackend):
    name = "serial"

    def _make_store(self) -> FrontierStore:
        config, app = self.config, self.app
        self._use_pallas = config.resolve_use_pallas()
        self._agg_kernel = config.resolve_aggregate_kernel()
        self._agg_bin = config.resolve_aggregate_bin()
        store = make_store(
            config.store, self.g,
            mode=app.mode,
            app_filter=programs.store_app_filter(app, self.g),
            use_pallas=self._use_pallas,
            interpret=config.pallas_interpret,
            device_budget_bytes=config.device_budget_bytes,
        )
        # device-resident aggregation needs alpha at pattern granularity:
        # apps overriding the per-row aggregation_filter keep the host path
        self._device_agg = (
            config.device_aggregate
            and app.wants_patterns
            and type(app).aggregation_filter is MiningApp.aggregation_filter
        )
        # level-2 placement (DESIGN.md §15): host_async needs a deferrable
        # table (loop joins at seal) — pruning/domain apps degrade to the
        # synchronous host reference, bit-identical either way.
        self._canon_placement = config.resolve_canonical_placement()
        if self._canon_placement == "host_async" and not (
            self._device_agg and aggregation.async_level2_ok(app)
        ):
            self._canon_placement = "host"
        if config.canonical_memo_cap is not None:
            pattern_lib.set_memo_cap(config.canonical_memo_cap)
        #: cross-batch level-1 merge capacity, grown pow2 on observed
        #: overflow (the unclamped distinct count rides the one drain)
        self._agg_qcap = max(config.agg_qcap, 1)
        self._run_qcap = next_pow2(self._agg_qcap)
        # child codes / level-1 partials computed in the chunk program are
        # only reusable when the next superstep re-materialises exactly the
        # appended rows in order — true for the raw store (also under a
        # spill budget), not for ODAG extraction (which may resurrect
        # pattern-pruned rows).
        order_preserving = (
            config.async_chunks and app.wants_patterns and store.kind == "raw"
        )
        self.with_patterns = order_preserving and not self._device_agg
        # FSM (wants_domains) re-bins at wave time instead: the domain
        # scatter needs the per-row local-vertex table, which partials
        # deliberately drop
        self.with_aggregates = (
            order_preserving and self._device_agg and not app.wants_domains
        )
        self._expand_fn = programs.make_expand_fn(
            app, app.mode,
            use_pallas=self._use_pallas,
            fused=config.fused_expand,
            interpret=config.pallas_interpret,
            compact_kernel=config.resolve_compact_kernel(),
            with_patterns=self.with_patterns,
            with_aggregates=self.with_aggregates,
            agg_qcap=self._agg_qcap,
            aggregate_kernel=self._agg_kernel,
            aggregate_bin=self._agg_bin,
            with_local_verts=app.wants_domains,
        )
        self._cache_before = programs.jit_cache_size(self._expand_fn)
        self._signatures = set()
        self._lvl1 = None
        self._table = None
        self._gather_probe = (
            self._make_gather_probe()
            if isinstance(self.g, PartitionedGraph)
            else None
        )
        return store

    def _make_gather_probe(self):
        """Jitted tile-gather probe for ``StepStats.t_gather`` (DESIGN.md
        §12): ``build_tile_view`` runs INSIDE the fused chunk program, so
        its share of ``t_expand`` is only separable by re-running the
        gather stage standalone — a probe dispatch paid exclusively under
        ``trace_sync=True`` (the diagnostic mode)."""
        config, mode = self.config, self.app.mode
        use_pallas = self._use_pallas
        compact = config.resolve_compact_kernel()
        interpret = config.pallas_interpret

        @jax.jit
        def probe(g, members, n_valid):
            view = explore.build_tile_view(
                g, members, n_valid, mode,
                use_pallas=use_pallas,
                compact_kernel=compact,
                interpret=interpret,
            )
            return view.nbr_t

        return probe

    # -- superstep hooks ----------------------------------------------------
    def begin_step(self, store, st) -> List[np.ndarray]:
        self._waves = list(store.chunks())
        self._wave_dev: List[Optional[jnp.ndarray]] = [None] * len(self._waves)
        return self._waves

    def quick_codes(self, blocks, size):
        codes_parts, lv_parts = [], []
        for wi, w in enumerate(blocks):
            self._wave_dev[wi] = jnp.asarray(np.ascontiguousarray(w))
            qp = programs.quick_patterns(
                self.g, self.app.mode, self._wave_dev[wi],
                jnp.full((len(w),), size, dtype=jnp.int32),
            )
            codes_parts.append(obs.to_host(qp.codes))
            lv_parts.append(obs.to_host(qp.local_verts))
            if self.config.device_budget_bytes is not None:
                # SpillStore contract: one budget wave resident at a time —
                # expansion re-uploads its own wave
                programs.retire(self._wave_dev[wi])
                self._wave_dev[wi] = None
        codes = (
            np.concatenate(codes_parts)
            if codes_parts else np.zeros((0, 3), np.int64)
        )
        lv = (
            np.concatenate(lv_parts)
            if lv_parts
            else np.zeros((0, pattern_lib.MAX_PATTERN_VERTICES), np.int32)
        )
        return codes, lv

    def aggregate(self, codes, lv, st):
        # host-resident level 1 (reference path): placement "device" still
        # routes the miss batch through the refine kernel; "host_async" has
        # no deferrable table here and runs synchronously (bit-identical).
        canon_fn = (
            canonical_refine.make_canon_fn(
                use_kernel=self._agg_kernel,
                interpret=self.config.pallas_interpret,
            )
            if self._canon_placement == "device"
            else None
        )
        agg, canon_slot = aggregation.aggregate_rows(
            self.g.n, codes, lv, self.app.wants_domains, canon_fn=canon_fn
        )
        obs.set_stat(st, "n_quick_patterns", agg.n_quick)
        obs.set_stat(st, "n_canonical_patterns", agg.n_canonical)
        obs.set_stat(st, "n_iso_checks", agg.n_iso_checks)
        return agg, canon_slot

    # -- device-resident aggregation (DESIGN.md §10) ------------------------
    @_charges_reads
    def aggregate_step(self, blocks, size, carried, st):
        if not self._device_agg:
            return super().aggregate_step(blocks, size, carried, st)
        app = self.app
        n_frontier = sum(len(blk) for blk in blocks)
        lvl1 = (
            carried
            if isinstance(carried, aggregation.DeviceLevel1)
            and carried.rows == n_frontier
            else None
        )
        if lvl1 is None:
            with obs.span("aggregate.bin"):
                lvl1 = self._fold_waves(blocks, size)
        with obs.span("aggregate.drain"):
            res = lvl1.finish()
        if res is not None and faults_lib.take(
            self.config.faults, "aggregate", st.step, "saturate"
        ):
            # injected count saturation (DESIGN.md §13): discard the packed
            # result exactly as a tripped saturation flag would, forcing
            # the wide re-fold below — same recovery path, deterministic
            res = None
        if res is None:
            # a chunk partial or eager compaction overflowed: the carried
            # state is unrecoverable on device, so re-fold from the waves
            # (whose pristine partials re-merge at the exact capacity).
            # The unclamped distinct count rode the same corruption-flag
            # drain, so grow ``agg_qcap`` pow2-style and rebuild the chunk
            # program at the larger partial capacity — later supersteps
            # keep carrying partials instead of silently falling back to
            # wave re-bins for the rest of the run (labeled graphs like
            # mico cross the default cap with ~37k size-3 quick codes;
            # BENCH_5's carried-partial regression).
            self._run_qcap = max(
                self._run_qcap, next_pow2(max(lvl1.observed_n, 1))
            )
            self._grow_carried_partials(self._run_qcap)
            with obs.span("aggregate.bin"):
                lvl1 = self._fold_waves(blocks, size)
            with obs.span("aggregate.drain"):
                res = lvl1.finish()
        uniq, counts_q, nbytes = res
        self._run_qcap = max(self._run_qcap, next_pow2(max(lvl1.observed_n, 1)))
        obs.count(st, "bytes_to_host", nbytes)
        placement = self._canon_placement
        if placement == "host_async":
            # overlap: the loop joins the pending future at the seal
            # boundary, after the next expansion has been enqueued.
            # Eligibility (async_level2_ok) guarantees no pruning reads
            # the table this step, so carrying no _table is safe.
            with obs.span("canonicalize.submit", n_quick=len(uniq)):
                pending = aggregation.submit_level2(uniq, counts_q)
            self._lvl1, self._table = lvl1, None
            self._agg_blocks, self._agg_size = blocks, size
            return pending, None
        t0 = time.perf_counter()
        with obs.span("canonicalize", placement=placement, n_quick=len(uniq)):
            if placement == "device" and lvl1._final is not None and len(uniq):
                u, c, uv, fcap, _n = lvl1._final
                table, counts, nbytes2 = aggregation.device_level2(
                    u, c, uv, fcap, len(uniq), uniq, counts_q,
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
            with obs.span("aggregate.domains", n_canonical=pc):
                bm = self._scatter_domains(lvl1, table, st)
            supports = aggregation.min_image_support(
                bm, table.canon_n_verts, table.canon_orbits
            )
        else:
            supports = counts.copy()
        agg = aggregation.build_step_aggregates(
            table, counts, supports, len(uniq), st
        )
        self._lvl1, self._table = lvl1, table
        self._agg_blocks, self._agg_size = blocks, size
        return agg, None

    def _grow_carried_partials(self, qcap: int) -> None:
        """Swap the chunk program for one whose per-chunk level-1 partial
        is bound at the grown pow2 ``qcap`` (process-wide cache makes this
        cheap when seen before), keeping the compile accounting consistent
        across the swap. Carried partials stay ON — the old behaviour
        (dropping to wave re-bins for the rest of the run) silently
        forfeited the O(Q) aggregation path on every labeled graph whose
        distinct quick-code count crossed the default cap once."""
        if not self.with_aggregates or qcap <= self._agg_qcap:
            return
        old = programs.jit_cache_size(self._expand_fn)
        done = (
            old - self._cache_before
            if old is not None and self._cache_before is not None
            else None
        )
        self._agg_qcap = qcap
        self._expand_fn = programs.make_expand_fn(
            self.app, self.app.mode,
            use_pallas=self._use_pallas,
            fused=self.config.fused_expand,
            interpret=self.config.pallas_interpret,
            compact_kernel=self.config.resolve_compact_kernel(),
            with_patterns=self.with_patterns,
            with_aggregates=True,
            agg_qcap=self._agg_qcap,
            aggregate_kernel=self._agg_kernel,
            aggregate_bin=self._agg_bin,
            with_local_verts=self.app.wants_domains,
        )
        new = programs.jit_cache_size(self._expand_fn)
        self._cache_before = (
            new - done if new is not None and done is not None else None
        )

    def _fold_waves(self, blocks, size) -> aggregation.DeviceLevel1:
        """Device re-bin of the materialised frontier: quick patterns per
        wave (on the upload the expansion reuses) folded into one
        :class:`DeviceLevel1`; per-wave slot ids and local-vertex tables
        stay device-resident for the FSM domain scatter / alpha masks."""
        config = self.config
        lvl1 = aggregation.DeviceLevel1(
            merge_cap=self._run_qcap,
            use_kernel=self._agg_kernel,
            bin_method=self._agg_bin,
            interpret=config.pallas_interpret,
        )
        wave_dev = (
            self._wave_dev
            if blocks is self._waves
            else [None] * len(blocks)
        )
        for wi, w in enumerate(blocks):
            if not len(w):
                continue
            if wave_dev[wi] is None:
                wave_dev[wi] = jnp.asarray(np.ascontiguousarray(w))
            qp = programs.quick_patterns(
                self.g, self.app.mode, wave_dev[wi],
                jnp.full((len(w),), size, dtype=jnp.int32),
            )
            lvl1.fold_rows(
                qp.codes,
                qp.local_verts if self.app.wants_domains else None,
            )
            if config.device_budget_bytes is not None:
                programs.retire(wave_dev[wi])
                wave_dev[wi] = None
        return lvl1

    def _scatter_domains(self, lvl1, table, st) -> np.ndarray:
        """FSM phase 2: scatter every batch's vertices into the canonical
        domain bitmap on device; only the (Pc, 8, N) result crosses."""
        pc = len(table.canon_codes)
        pc_cap = next_pow2(max(pc, 1))
        n = self.g.n
        q2c, si = aggregation.level2_device_tables(table, lvl1.final_cap)
        kmax = pattern_lib.MAX_PATTERN_VERTICES
        flat = jnp.zeros((pc_cap * kmax * n + 1,), dtype=bool)
        for i in range(len(lvl1.batches)):
            flat = aggregation.scatter_canon_bitmaps(
                flat, lvl1.batch_slots(i), lvl1.batches[i][1],
                q2c, si, pc_cap, n,
            )
        bm = obs.to_host(flat[:-1].reshape(pc_cap, kmax, n)[:pc])
        obs.count(st, "bytes_to_host", bm.nbytes)
        return bm

    @_charges_reads
    def alpha_rows(self, pk, st):
        """Per-row alpha from the per-pattern verdict: gather the (padded)
        per-quick-slot keep table through the device-resident slot ids —
        the O(B) bool mask is the only per-row state that crosses, and only
        because pruning actually fired."""
        lvl1, table = self._lvl1, self._table
        if not lvl1.batches:
            # carried partials hold no per-row slots: re-bin the waves (the
            # distinct table is sorted, so slot order matches `table`)
            lvl1 = self._fold_waves(self._agg_blocks, self._agg_size)
            res = lvl1.finish()
            obs.count(st, "bytes_to_host", res[2])
            self._lvl1 = lvl1
        q = len(table.quick_codes)
        pk_q = np.zeros(lvl1.final_cap, dtype=bool)
        pk_q[:q] = np.asarray(pk, dtype=bool)[table.quick_to_canon]
        pk_dev = jnp.asarray(pk_q)
        parts = [
            pk_dev[lvl1.batch_slots(i)] for i in range(len(lvl1.batches))
        ]
        if not parts:
            return np.zeros((0,), dtype=bool)
        # gather per wave, concatenate on device, drain ONCE — per-wave
        # host round trips would creep back in exactly the spill case
        # (many waves) this pipeline keeps at O(1) drains
        mask = obs.to_host(
            parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        )
        obs.count(st, "bytes_to_host", mask.nbytes)
        return mask

    def prune(self, blocks, alpha):
        # pruned rows invalidate the device-resident waves
        programs.retire(*[wd for wd in self._wave_dev if wd is not None])
        blocks = super().prune(blocks, alpha)
        self._waves = blocks
        self._wave_dev = [None] * len(blocks)
        return blocks

    @_charges_reads
    def expand(self, store, blocks, size, st):
        config = self.config
        waves = blocks
        # the device-upload cache is valid only for the exact block list
        # this backend handed out (begin_step) or pruned — anything else
        # re-uploads rather than risking stale rows
        wave_dev = (
            self._wave_dev
            if blocks is self._waves
            else [None] * len(blocks)
        )
        carried = None
        if config.async_chunks:
            #: the NEXT superstep's level-1 state, folded from the chunk
            #: partials as the drain windows complete (DESIGN.md §10)
            lvl1 = (
                aggregation.DeviceLevel1(
                    merge_cap=self._run_qcap,
                    use_kernel=self._agg_kernel,
                    bin_method=self._agg_bin,
                    interpret=config.pallas_interpret,
                )
                if self.with_aggregates
                else None
            )
            if config.device_budget_bytes is not None and len(waves) > 1:
                # SpillStore contract (DESIGN.md §7): at most one budget
                # wave device-resident at a time — pipeline and drain one
                # wave per pass (syncs O(waves), i.e. O(frontier/budget),
                # still independent of the chunk count) and retire each
                # wave's buffers before the next is uploaded.
                parts = []
                for wi in range(len(waves)):
                    sub_dev = [wave_dev[wi]]
                    c, self.capacity = self._expand_fused(
                        store, [waves[wi]], sub_dev, size, self.capacity,
                        st, lvl1,
                    )
                    programs.retire(sub_dev[0])
                    wave_dev[wi] = None
                    if c is not None:
                        parts.append(c)
                if self.with_patterns:
                    carried = (
                        (
                            np.concatenate([p[0] for p in parts]),
                            np.concatenate([p[1] for p in parts]),
                        )
                        if parts
                        else None
                    )
                else:
                    carried = lvl1
            else:
                c, self.capacity = self._expand_fused(
                    store, waves, wave_dev, size, self.capacity, st, lvl1
                )
                carried = lvl1 if self.with_aggregates else c
        else:
            self._expand_legacy(store, waves, size, st)
        # every chunk has been drained — the step's device waves are dead
        programs.retire(*[wd for wd in wave_dev if wd is not None])
        return carried

    def end_step(self, store, st) -> None:
        # release last step's retained level-1 batch state (slot ids,
        # FSM local-vertex tables) and the materialised block list kept
        # for the alpha re-fold, before the checkpoint hook
        self._lvl1 = None
        self._table = None
        self._agg_blocks = None

    def finalize(self, stats) -> None:
        stats.chunk_signatures = sorted(self._signatures)
        cache_after = programs.jit_cache_size(self._expand_fn)
        stats.n_compiles = (
            cache_after - self._cache_before
            if self._cache_before is not None and cache_after is not None
            else len(self._signatures)
        )

    # -- the fused pipeline (DESIGN.md §8) ----------------------------------
    def _rec(self, out, used_cap):
        """Name one chunk program's outputs (layout differs between the
        carried-codes and carried-partials modes)."""
        if self.with_aggregates:
            children, count, u, c, n, ngen, ncanon = out
            return {"children": children, "count": count,
                    "agg": (u, c, n), "ngen": ngen, "ncanon": ncanon,
                    "used_cap": used_cap}
        children, count, codes, lv, ngen, ncanon = out
        return {"children": children, "count": count, "codes": codes,
                "lv": lv, "ngen": ngen, "ncanon": ncanon,
                "used_cap": used_cap}

    def _retire_outputs(self, p) -> None:
        programs.retire(p["children"])
        if "codes" in p:
            programs.retire(p["codes"], p["lv"])
        if "agg" in p:
            programs.retire(*p["agg"][:2])

    def _dispatch(self, st, size, bucket, chunk, n_valid, cap):
        """One chunk-program call at output capacity ``cap``: records its
        signature and counts its candidate lanes, the padded (bucket, k, D)
        table the chip computes (k = 2·size endpoint slots in edge mode)."""
        self._signatures.add((size, bucket, cap))
        k = size if self.app.mode == "vertex" else 2 * size
        obs.count(st, "n_lanes", bucket * k * self.g.max_degree)
        with obs.span("expand.dispatch", rows=bucket, cap=cap):
            return self._expand_fn(self.g, chunk, n_valid, out_cap=cap)

    @staticmethod
    def _fetch(st, x, cnt: int, dtype=None) -> np.ndarray:
        """Drain the first ``cnt`` rows of a chunk output to the host as a
        device-side prefix slice: the padding never crosses (same contract
        as ``store.resolve_rows``)."""
        with obs.span("expand.fetch", rows=cnt):
            out = obs.to_host(x[:cnt], dtype)
        obs.count(st, "rows_to_host", out.nbytes)
        return out

    def _expand_fused(self, store, waves, wave_dev, size, cap, st, lvl1):
        """One *pilot* chunk calibrates the step's output-capacity bucket
        (sync 1 — the PR-2 loop instead discovers capacity growth once per
        chunk); the remaining chunks dispatch back-to-back with counts left
        on device and drain in stacked reads of ``_DRAIN_WINDOW`` chunks
        (one more sync per window, a single one for typical steps).
        Compaction counts are exact (never clamped to the capacity), so
        overshot chunks are re-dispatched at their exact pow2 bucket
        without any further sync. As a window drains, its children fold
        into the store via device-side prefix slices (only valid rows cross
        to the host), and the next step's pattern state folds device-side:
        carried child quick codes (``with_patterns``) or pre-binned level-1
        partials into ``lvl1`` (``with_aggregates``, DESIGN.md §10); every
        buffer of the window is retired."""
        g, config = self.g, self.config
        with_patterns, with_aggregates = self.with_patterns, self.with_aggregates
        with obs.span("expand.plan"):
            chunks = list(
                programs.iter_chunks(waves, wave_dev, config.chunk_size, size)
            )
        obs.count(st, "n_chunks", len(chunks))
        if not chunks:
            return None, cap
        if self._gather_probe is not None and obs.sync_active():
            # trace_sync probe (DESIGN.md §12): the tile gather runs INSIDE
            # the fused chunk program; its share of t_expand is only
            # separable by re-running the gather standalone per chunk —
            # paid exclusively in the diagnostic sync mode
            for ch in chunks:
                obs.count(
                    st, "t_gather",
                    obs.probe_time(self._gather_probe, g, ch[4], ch[5]),
                )

        # ---- pilot: sync 1 calibrates the capacity bucket for the step --
        _, _, cb0, bucket0, chunk0, n_valid0 = chunks[0]
        out = self._rec(
            self._dispatch(st, size, bucket0, chunk0, n_valid0, cap), cap
        )
        with obs.span("expand.sync"):
            c0 = int(obs.to_host(out["count"]))
        obs.count(st, "n_host_syncs", 1)
        if c0 > cap:
            self._retire_outputs(out)
            cap = next_pow2(c0)
            out = self._rec(                       # count known exact
                self._dispatch(st, size, bucket0, chunk0, n_valid0, cap), cap
            )
        # scale the pilot count to a full bucket for the remaining chunks; a
        # chunk that still overshoots is re-dispatched individually below
        est = -((-c0 * bucket0) // max(cb0, 1))        # ceil(c0 * bucket0 / cb0)
        step_cap = max(next_pow2(max(est, 1)), 64)

        codes_parts, lv_parts = [], []

        def drain(pending):
            """One stacked control sync for a window of dispatched chunks,
            exact-cap overflow retries, then fold + retire."""
            with obs.span("expand.sync", chunks=len(pending)):
                meta = obs.to_host(
                    jnp.stack([
                        s for p, _ in pending
                        for s in (p["count"], p["ngen"], p["ncanon"])
                    ])
                ).reshape(-1, 3)
            obs.count(st, "n_host_syncs", 1)
            counts = meta[:, 0]
            obs.count(st, "n_generated", int(meta[:, 1].sum()))
            obs.count(st, "n_canonical", int(meta[:, 2].sum()))
            for i, (p, ch) in enumerate(pending):
                if counts[i] <= p["used_cap"]:
                    continue
                self._retire_outputs(p)             # oversubscribed outputs
                retry_cap = next_pow2(int(counts[i]))
                p2 = self._rec(
                    self._dispatch(st, size, ch[3], ch[4], ch[5], retry_cap),
                    retry_cap,
                )
                pending[i] = (p2, ch)
            for i, (p, ch) in enumerate(pending):
                cnt = int(counts[i])
                programs.retire(ch[4], ch[5])       # chunk inputs are dead now
                if cnt:
                    rows = self._fetch(st, p["children"], cnt, np.int32)
                    if with_patterns:
                        codes_parts.append(self._fetch(st, p["codes"], cnt))
                        lv_parts.append(self._fetch(st, p["lv"], cnt))
                    with obs.span("expand.fold", rows=cnt):
                        store.append(rows)
                        if with_aggregates and lvl1 is not None:
                            # fold the chunk's pre-binned partial; the
                            # buffers are consumed by the merge (refs
                            # dropped there)
                            u, c, n = p["agg"]
                            acap = min(p["used_cap"], self._agg_qcap)
                            lvl1.fold_partial(
                                u, c, n, acap, cnt,
                                may_overflow=p["used_cap"] > acap,
                            )
                            p.pop("agg")
                self._retire_outputs(p)

        pending = [(out, chunks[0])]
        for ch in chunks[1:]:
            _, _, _, bucket_i, chunk_i, n_valid_i = ch
            p = self._rec(
                self._dispatch(
                    st, size, bucket_i, chunk_i, n_valid_i, step_cap
                ),
                step_cap,
            )
            pending.append((p, ch))
            if len(pending) >= _DRAIN_WINDOW:
                drain(pending)
                pending = []
        if pending:
            drain(pending)
        cap = max(cap, step_cap)

        carried = None
        if with_patterns and codes_parts:
            carried = (np.concatenate(codes_parts), np.concatenate(lv_parts))
        return carried, cap

    # -- the PR-2 chunk loop, preserved as the measured baseline -----------
    def _expand_legacy(self, store, waves, size, st):
        """The PR-2 chunk loop, preserved bit-for-bit
        (``benchmarks/bench_superstep.py``): every chunk is sliced and
        padded on the host and re-uploaded (even when aggregation already
        uploaded the wave — the double upload the fused pipeline removes),
        one blocking ``int(count)`` host sync per chunk plus one per
        capacity retry, the capacity bucket reset every superstep, children
        forced through ``np.asarray`` per chunk."""
        config = self.config
        cap = max(config.initial_capacity, 1)
        for w in waves:
            for lo in range(0, len(w), config.chunk_size):
                with obs.span("expand.plan"):
                    chunk = np.asarray(w[lo : lo + config.chunk_size])
                    cb = int(chunk.shape[0])
                    bucket = min(config.chunk_size, next_pow2(max(cb, 1)))
                    pad = bucket - cb
                    if pad:
                        chunk = np.concatenate(
                            [chunk, np.full((pad, size), -1, np.int32)],
                            axis=0,
                        )
                    n_valid = jnp.concatenate(
                        [jnp.full((cb,), size, jnp.int32),
                         jnp.zeros((pad,), jnp.int32)]
                    )
                    chunk = jnp.asarray(chunk)
                obs.count(st, "n_chunks", 1)
                while True:
                    out = self._dispatch(st, size, bucket, chunk, n_valid, cap)
                    children, count = out[0], out[1]
                    ngen, ncanon = out[-2], out[-1]
                    with obs.span("expand.sync"):
                        count = int(obs.to_host(count))
                    obs.count(st, "n_host_syncs", 1)
                    if count <= cap:
                        break
                    programs.retire(children)
                    cap = next_pow2(count)
                with obs.span("expand.sync"):
                    ngen = int(obs.to_host(ngen))
                    ncanon = int(obs.to_host(ncanon))
                obs.count(st, "n_generated", ngen)
                obs.count(st, "n_canonical", ncanon)
                if count:
                    rows = self._fetch(st, children, count)
                    with obs.span("expand.fold", rows=count):
                        store.append(rows)
