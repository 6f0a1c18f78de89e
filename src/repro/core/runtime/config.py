"""The one run configuration for the unified superstep runtime (DESIGN.md §9).

``RunConfig`` supersedes the two hand-maintained config dataclasses the
engines grew (``EngineConfig`` in ``core/engine.py`` and ``DistConfig`` in
``core/distributed.py``): every knob, every ``resolve_*`` helper, and the
pow2 capacity-bucket arithmetic now live here exactly once. The old names
are kept as empty subclasses (deprecation shims), so every existing call
site keeps working and old kwargs resolve identically (tested in
``tests/test_runtime.py``).

Serial-only knobs (``chunk_size``, ``device_budget_bytes``) are ignored by
the shard-map backend; distributed-only knobs (``axes``,
``naive_aggregation``) are ignored by the serial backend — a config is a
description of the *run*, the backend picks what applies.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from repro.kernels.dispatch import resolve_canonical_placement, resolve_halo


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (1 for x <= 1): THE capacity-bucket rule.

    Chunk widths and output capacities are bucketed to powers of two so XLA
    recompiles only per bucket (DESIGN.md §8) — shared by both backends and
    the benchmarks."""
    return 1 << max(0, (int(x) - 1).bit_length())


def _static_native() -> bool:
    """Pre-resolution fallback for the kernel knobs: Pallas only where it
    compiles to native code (TPU — see ``costmodel.static_table`` for the
    rationale).  Configs that went through ``costmodel.resolve`` never hit
    this — every decided knob is concrete by the time a backend builds its
    programs; this keeps direct ``resolve_*`` callers (faults ladder,
    benches) working on an unresolved config."""
    return jax.default_backend() == "tpu"


@dataclasses.dataclass
class RunConfig:
    """Configuration of one mining run, backend-agnostic (DESIGN.md §9)."""

    #: serial chunk bound: frontier rows per expansion program, or, where
    #: candidate lanes are packed (vertex mode, DESIGN.md §8), a lane
    #: budget of ``chunk_size * k * D`` per program (k members, max degree D)
    chunk_size: int = 4096
    initial_capacity: int = 4096  # starting output-capacity bucket (per shard
                                  # in the distributed backend)
    max_steps: int = 16           # hard cap on exploration depth
    #: route the Alg.-2 canonicality check through the Pallas kernel
    #: (VMEM-sized graphs, vertex mode). None -> auto: the cost model
    #: decides (statically: on for TPU, the one compiled target).
    use_pallas: Optional[bool] = None
    #: with use_pallas, also fuse candidate validity + dedup + Alg.-2 into
    #: the single-pass expand_canonical kernel (vertex mode).
    fused_expand: bool = False
    #: Pallas interpret override; None -> auto per backend (compiled on
    #: TPU, interpreter elsewhere; True is refused on TPU).
    pallas_interpret: Optional[bool] = None
    #: how the frontier lives between supersteps: "raw" keeps the dense
    #: embedding list, "odag" stores per-size ODAGs (paper §5.2) and
    #: re-materialises via cost-balanced extraction (§5.3).
    store: str = "raw"
    #: device byte budget for one materialised frontier wave; when set, the
    #: frontier store is wrapped in a SpillStore and each superstep is mined
    #: in waves of at most this many bytes of embedding rows (frontiers
    #: larger than device memory). None -> one wave per step. Serial
    #: backend only.
    device_budget_bytes: Optional[int] = None
    #: fused superstep pipeline (DESIGN.md §8): chunk programs return
    #: children + counts + child quick-pattern codes in one device pass,
    #: counts stay device-resident and the host drains them ONCE per
    #: superstep (O(1) host syncs instead of O(chunks)). False = the PR-2
    #: chunk loop (one host sync per chunk, separate quick-pattern pass) —
    #: kept as the measured baseline. None -> cost model (DESIGN.md §14):
    #: the calibration pilot compares the legacy loop's per-chunk tax
    #: (sync + upload + quick-pattern pass) against the fused pipeline's.
    async_chunks: Optional[bool] = None
    #: route chunk compaction through the Pallas stream-compaction kernel
    #: (block prefix-sum + scatter, ``kernels/compact.py``) instead of the
    #: jnp nonzero gather. None -> auto: on where Pallas compiles to
    #: native code (TPU), off on CPU where the interpreter would lose.
    compact_kernel: Optional[bool] = None
    #: device-resident level-1 pattern aggregation (DESIGN.md §10): quick
    #: codes are binned into per-pattern counts (and FSM domain bitmaps) on
    #: device, and only O(#patterns) bytes cross to the host for level-2
    #: canonicalisation. False = the host reference path
    #: (``aggregation.aggregate_rows``), which drains the full frontier's
    #: codes each superstep. Apps overriding the per-row
    #: ``aggregation_filter`` (instead of ``pattern_filter``) fall back to
    #: the host path automatically — alpha then needs per-row slots.
    #: None -> cost model: measured per-row device fold+merge cost vs
    #: per-row host drain cost decides the placement per backend.
    device_aggregate: Optional[bool] = None
    #: route the level-1 segment-unique/reduce through the Pallas kernel
    #: (``kernels/aggregate.py``; the row sort stays on XLA's tuned sort).
    #: None -> auto: on where Pallas compiles natively (TPU), off on CPU.
    aggregate_kernel: Optional[bool] = None
    #: row-binning algorithm of the device level-1 bin: "sort" keeps XLA's
    #: 2-key ``lax.sort`` (``kernels/aggregate.py``), "radix" routes
    #: through the LSB-radix / fused-key bucket bin
    #: (``kernels/radix_bin.py``) — measured faster on CPU where XLA's
    #: variadic sort is slow. None -> cost model picks per backend.
    aggregate_bin: Optional[str] = None
    #: where level-2 canonicalisation of the distinct quick-code table runs
    #: (DESIGN.md §15): "device" refines all O(Q) codes in a batched
    #: permutation kernel inside the aggregation program
    #: (``kernels/canonical_refine.py``); "host" is the memoised numpy
    #: batch on the critical path (the reference); "host_async" runs that
    #: same host batch on a background thread overlapped with the next
    #: superstep's expansion and joined at the seal boundary (apps that
    #: prune on patterns mid-step — FSM's support filter — or consume
    #: domains fall back to "host" silently: alpha needs the table).
    #: None -> cost model: the calibration pilot times device refine vs
    #: host batch on the pilot's distinct codes and picks per backend.
    canonical_placement: Optional[str] = None
    #: LRU cap of the process-wide quick->canonical memo
    #: (``pattern.set_memo_cap``). None keeps ``pattern.DEFAULT_MEMO_CAP``
    #: (2^20 entries); labeled-graph services that mine many graphs can
    #: lower it to bound resident memo bytes.
    canonical_memo_cap: Optional[int] = None
    #: how the ``None``/auto knobs above resolve (DESIGN.md §14): "auto"
    #: runs the pilot-calibrated cost model (probe timings pick the
    #: fastest implementation per phase per backend, cached per
    #: (backend, app, graph) signature); "off" pins the static defaults
    #: (fused + device aggregation, Pallas on TPU only); "force_device" /
    #: "force_host" pin the placement extremes so every dispatch path is
    #: reachable regardless of measurements.
    cost_model: str = "auto"
    #: directory the calibrated decision tables persist in (JSON, one file
    #: per (backend, platform, app, graph, config) signature) so repeat
    #: runs in fresh processes skip the calibration pilot. None -> the
    #: table is cached process-wide only.
    cost_model_dir: Optional[str] = None
    #: graphs with fewer edges than this resolve through the static table
    #: without calibrating — a unit-test-sized run must never pay a pilot.
    cost_model_min_edges: int = 2048
    #: starting capacity of the cross-batch level-1 merge table (distinct
    #: quick patterns per superstep). Like the output-capacity bucket it
    #: grows by pow2 on overflow — the unclamped distinct count rides the
    #: one aggregation drain, so growth costs a re-merge (or a wave
    #: re-fold), never an extra sync. Labeled graphs with tens of
    #: thousands of quick patterns can set it higher up front.
    agg_qcap: int = 4096
    #: number of graph shards of the partitioned layout (DESIGN.md §11):
    #: the device graph becomes per-device CSR shards + packed adjacency
    #: tiles (``core.graph.PartitionedGraph``) and the fused pipeline opens
    #: with a halo-tile gather instead of whole-graph lookups. None keeps
    #: the replicated ``DeviceGraph`` (the reference layout). The serial
    #: backend mines any shard count as virtual shards; the shard-map
    #: backend requires it to equal the mesh worker count (the shard axis
    #: IS the mesh axis) and exchanges halos in-program.
    graph_partition: Optional[int] = None
    #: partition boundary placement: "degree" balances adjacency payload
    #: across shards, "vertex" splits the id space evenly.
    partition_balance: str = "degree"
    #: halo-exchange strategy of the partitioned shard-map superstep:
    #: "alltoall" (position-aligned request/response all-to-all, O(halo)
    #: bytes per worker), "gather" (ragged all-gather of the shard tables,
    #: O(n) fallback), or None/"auto" -> "alltoall"
    #: (``kernels.dispatch.resolve_halo``).
    halo: Optional[str] = None
    #: mesh axes the shard-map backend shards the frontier over.
    axes: tuple = ("data",)
    #: disable two-level aggregation (§Perf baseline, distributed backend):
    #: every worker all-gathers all embeddings' quick codes and
    #: canonicalises each embedding's pattern itself — the paper's Fig.11
    #: naive scheme.
    naive_aggregation: bool = False
    #: directory for superstep-granular checkpoints (DESIGN.md §9): when
    #: set, the runtime writes {sealed store payload, stats, patterns,
    #: superstep cursor, app+graph fingerprints} at the seal boundary and
    #: ``runtime.resume`` continues from the latest one — under any worker
    #: count (elastic restore: re-partition happens at extraction time).
    checkpoint_dir: Optional[str] = None
    #: write a checkpoint every this-many supersteps (1 = every seal).
    checkpoint_every: int = 1
    #: collect host phase spans + metrics for this run (DESIGN.md §12).
    #: The default False path adds ZERO device syncs and no span
    #: allocation — the observability layer's hard contract, guarded by
    #: ``benchmarks/bench_obs.py`` and ``tests/test_obs.py``.
    trace: bool = False
    #: directory the traced run exports to: a Perfetto-loadable Chrome
    #: trace (``run-<pid>-<seq>.trace.json``) plus a live-tailable JSONL
    #: event stream (``.events.jsonl``). ``trace=True`` with no directory
    #: keeps the spans in memory only (``SuperstepRuntime.observer``).
    trace_dir: Optional[str] = None
    #: blocking ``block_until_ready`` phase boundaries: host phase laps
    #: measure device COMPLETION instead of dispatch, and the in-program
    #: tile-gather / halo-exchange stages get probe-measured into
    #: ``StepStats.t_gather``/``t_exchange``. Diagnostic mode — it
    #: serialises the pipeline; never implied by ``trace`` alone.
    trace_sync: bool = False
    #: print one structured progress line every this-many supersteps
    #: (0 = silent). Works with or without ``trace``.
    log_every: int = 0
    #: deterministic fault-injection plan (DESIGN.md §13): a
    #: ``runtime.faults.FaultPlan`` of (phase, superstep, kind) triples
    #: tripped at the loop's phase boundaries and the halo-exchange path.
    #: None (the default) compiles to a single attribute read per phase —
    #: production runs pay nothing. Test/chaos tooling only.
    faults: Optional[object] = None
    #: retry budget of ``run_supervised`` (DESIGN.md §13): how many times a
    #: failed attempt restarts from the last *valid* checkpoint before the
    #: failure is re-raised. 0 = one attempt, no retries.
    max_retries: int = 3
    #: base seconds of the supervisor's exponential backoff: retry k sleeps
    #: ``retry_backoff * 2**(k-1)``. 0 = retry immediately (tests/benches).
    retry_backoff: float = 0.0
    #: keep-last-K checkpoint retention (0 = keep every cut). K >= 2 keeps
    #: a rollback target when the newest checkpoint fails its checksum.
    keep_checkpoints: int = 0

    def resolve_use_pallas(self) -> bool:
        return _static_native() if self.use_pallas is None else self.use_pallas

    def resolve_compact_kernel(self) -> bool:
        return (
            _static_native()
            if self.compact_kernel is None
            else self.compact_kernel
        )

    def resolve_aggregate_kernel(self) -> bool:
        return (
            _static_native()
            if self.aggregate_kernel is None
            else self.aggregate_kernel
        )

    def resolve_aggregate_bin(self) -> str:
        return "sort" if self.aggregate_bin is None else self.aggregate_bin

    def resolve_halo(self) -> str:
        return resolve_halo(self.halo)

    def resolve_canonical_placement(self) -> str:
        return resolve_canonical_placement(self.canonical_placement)
