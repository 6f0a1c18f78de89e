"""Pilot-calibrated cost-model dispatch (DESIGN.md §14).

Every ``auto`` knob in :class:`repro.core.runtime.config.RunConfig` used to
resolve through a *static* heuristic (``use_pallas`` on TPU only, fused
pipeline + device aggregation everywhere).  BENCH_8 showed that static
placement is the wrong trade on at least one backend: on CPU the fused
pipeline with device aggregation ran at 0.51x of the legacy chunk loop,
while on TPU the same defaults are the right call.  This module turns the
PR-3 pilot chunk into a **calibration probe**: before the first superstep,
``resolve`` times the candidate implementation of each phase on a small,
real workload slice and fills every unset knob with the measured-fastest
choice.

The subsystem has four layers:

``DecisionTable``
    One record per (backend, platform): the concrete value of every
    decided knob plus the probe timings (µs) that justified it, and a
    ``source`` tag (``static`` / ``calibrated`` / ``cached`` /
    ``forced:<mode>``).  Recorded into ``RunStats.cost_model`` and the
    PR-7 trace so placement is observable after the fact.

``calibrate``
    The probe set.  (1) *expand ladder*: time
    ``explore.expand_and_compact`` on a pilot-sized size-1 chunk across
    {jnp, Pallas} x {jnp-compact, Pallas-compact} -> ``use_pallas``,
    ``compact_kernel``.  (2) *bin ladder*: quick codes of the pilot's
    children, tiled to ~64k rows, through ``kernels.aggregate.bin_rows``
    across {sort, radix} x {jnp, Pallas} -> ``aggregate_bin``,
    ``aggregate_kernel``.  (3) *placement*: per-row device fold+merge cost
    vs per-row host cost (transfer + numpy unique) -> ``device_aggregate``.
    (4) *async*: the legacy loop's per-chunk tax (host sync + chunk upload
    + separate quick-pattern pass) vs the fused pipeline's per-chunk tax
    (carried-partial fold when aggregating on device, ~nothing otherwise)
    -> ``async_chunks``.

caching
    Calibration runs once per (backend, platform, app fingerprint, graph
    fingerprint, config signature) — process-wide in ``_PROCESS_CACHE``
    and, when ``cost_model_dir`` is set, persisted as JSON so repeat runs
    (and repeat *processes*) skip the pilot entirely.  The fingerprints
    are the PR-4 checkpoint fingerprints, so "same graph, same app" means
    exactly what resume already means.

forcing
    ``cost_model="off"`` resolves like the pre-calibration static
    heuristic; ``"force_device"`` / ``"force_host"`` pin the placement
    knobs to the two extremes so every dispatch path stays reachable from
    tests regardless of what the probes would measure.  Explicitly set
    config knobs always win over the table — the model only fills knobs
    the user left at ``None``/auto.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import aggregate as agg_kernel
from repro.kernels.dispatch import COMPILED_BACKENDS

#: table schema version — bump to invalidate every persisted table.
#: v2: + canonical_placement (level-2 placement, DESIGN.md §15).
SCHEMA_VERSION = 2

#: the config knobs a table decides, in resolution order.
DECIDED_KNOBS = (
    "async_chunks",
    "device_aggregate",
    "use_pallas",
    "compact_kernel",
    "aggregate_kernel",
    "aggregate_bin",
    "canonical_placement",
)

COST_MODEL_MODES = ("auto", "off", "force_device", "force_host")

#: pilot rows the expand ladder times (a real size-1 chunk slice).
PROBE_CHUNK_ROWS = 256
#: rows the bin ladder times (pilot children tiled up). Every probe
#: program is compiled before it is timed, and on TPU v5e compile time
#: grows faster than linearly with array length (a 1-D gather of 131,072
#: indices takes 44 s to compile, of 8,192 indices 2.6 s), so the probes
#: stay at a size whose compile the pilot can afford.
PROBE_BIN_ROWS = 8192
#: expand-probe output capacity cap (same compile-time bound).
PROBE_OUT_CAP = 1 << 13
#: a non-jnp expand combo must be >=10% faster than plain jnp at probe
#: time to be chosen — near-ties are measurement noise, not wins.
EXPAND_HYSTERESIS = 0.9

_PROCESS_CACHE: Dict[tuple, "DecisionTable"] = {}


def _platform() -> str:
    return jax.default_backend()


@dataclasses.dataclass
class DecisionTable:
    """Concrete value of every decided knob + the timings that chose it."""

    backend: str                     # execution backend ("serial"/"shard_map")
    platform: str                    # jax.default_backend() at decision time
    source: str                      # static | calibrated | cached | forced:<m>
    async_chunks: bool = True
    device_aggregate: bool = True
    use_pallas: bool = False
    compact_kernel: bool = False
    aggregate_kernel: bool = False
    aggregate_bin: str = "sort"      # "sort" | "radix"
    canonical_placement: str = "host"  # "device" | "host" | "host_async"
    timings: Dict[str, float] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d["schema"] = SCHEMA_VERSION
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "DecisionTable":
        if d.get("schema") != SCHEMA_VERSION:
            raise ValueError(
                f"decision-table schema {d.get('schema')!r} != {SCHEMA_VERSION}"
            )
        kw = {f.name: d[f.name] for f in dataclasses.fields(cls) if f.name in d}
        return cls(**kw)

    def copy(self) -> "DecisionTable":
        return dataclasses.replace(self, timings=dict(self.timings))

    def decisions(self) -> Dict:
        """The knob -> choice mapping alone (what ``RunStats`` records)."""
        return {k: getattr(self, k) for k in DECIDED_KNOBS}


# ---------------------------------------------------------------------------
# static + forced tables
# ---------------------------------------------------------------------------

def static_table(backend_name: str, platform: Optional[str] = None,
                 source: str = "static") -> DecisionTable:
    """The pre-calibration defaults, exactly as the old static heuristic
    resolved them: fused pipeline + device aggregation everywhere, Pallas
    kernels only where they compile to native code (TPU, the one target
    their Mosaic-specific operands lower for), sort-based bin.  Small graphs (below ``cost_model_min_edges``) resolve here so a
    unit-test-sized run never pays a calibration pilot."""
    p = platform or _platform()
    native = p == "tpu"
    return DecisionTable(
        backend=backend_name, platform=p, source=source,
        async_chunks=True, device_aggregate=True,
        use_pallas=native, compact_kernel=native, aggregate_kernel=native,
        aggregate_bin="sort",
    )


def forced_table(mode: str, backend_name: str,
                 platform: Optional[str] = None) -> DecisionTable:
    """The ``force_device``/``force_host`` placement extremes: both keep
    the kernel knobs at their static defaults (forcing Pallas through the
    CPU interpreter would punish tests, not exercise new paths) and pin
    the placement knobs so each dispatch route is reachable by fiat."""
    t = static_table(backend_name, platform, source=f"forced:{mode}")
    if mode == "force_device":
        t.async_chunks = True
        t.device_aggregate = True
        t.aggregate_bin = "radix"
        t.canonical_placement = "device"
    elif mode == "force_host":
        t.async_chunks = False
        t.device_aggregate = False
        t.aggregate_bin = "sort"
        t.canonical_placement = "host"
    else:
        raise ValueError(f"unknown forced cost_model mode {mode!r}")
    return t


# ---------------------------------------------------------------------------
# cache keys: the PR-4 fingerprints + a config signature
# ---------------------------------------------------------------------------

def config_signature(config) -> str:
    """Hash of the config fields that change what calibration would
    measure (batch geometry + store discipline), NOT of the knobs the
    table decides — a user flipping ``aggregate_kernel`` must not fork the
    cache, it just overrides the table."""
    payload = repr((
        config.chunk_size, config.initial_capacity, config.agg_qcap,
        config.store, config.device_budget_bytes, config.graph_partition,
        config.fused_expand, config.pallas_interpret,
    ))
    return hashlib.sha1(payload.encode()).hexdigest()


def cache_key(backend_name: str, platform: str, app_fp: str, graph_fp: str,
              cfg_sig: str) -> tuple:
    return (SCHEMA_VERSION, backend_name, platform, app_fp, graph_fp, cfg_sig)


def _cache_path(cost_model_dir: str, key: tuple) -> str:
    _, backend, platform, app_fp, graph_fp, cfg_sig = key
    name = (
        f"costmodel-v{SCHEMA_VERSION}-{platform}-{backend}"
        f"-{app_fp[:10]}-{graph_fp[:10]}-{cfg_sig[:10]}.json"
    )
    return os.path.join(cost_model_dir, name)


def _load_cached(cost_model_dir: str, key: tuple) -> Optional[DecisionTable]:
    path = _cache_path(cost_model_dir, key)
    try:
        with open(path, "r", encoding="utf-8") as f:
            t = DecisionTable.from_dict(json.load(f))
    except (OSError, ValueError, KeyError, TypeError):
        return None
    t.source = "cached"
    return t


def _save_cached(cost_model_dir: str, key: tuple, table: DecisionTable) -> None:
    path = _cache_path(cost_model_dir, key)
    os.makedirs(cost_model_dir, exist_ok=True)
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(table.as_dict(), f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def clear_cache() -> None:
    """Drop the process-wide table cache (tests)."""
    _PROCESS_CACHE.clear()


# ---------------------------------------------------------------------------
# the probes
# ---------------------------------------------------------------------------

def _time_us(fn, repeat: int = 3) -> float:
    """Best-of-``repeat`` wall microseconds of ``fn()`` after one warm-up
    call (the warm-up eats compilation; best-of filters scheduler noise)."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def calibrate(g, app, config, backend_name: str) -> DecisionTable:
    """Run the probe set on a pilot-sized slice of the real workload and
    return the measured-fastest table.  A probe that fails (a kernel the
    device compiler refuses, say) raises: the same program would fail the
    run, and a table that silently routes around it would hide the fault
    behind a slower path."""
    from repro.core import explore
    from repro.core.runtime import programs

    platform = _platform()
    table = static_table(backend_name, platform, source="calibrated")
    timings = table.timings
    mode = app.mode
    interpret = config.pallas_interpret

    n0 = int(g.n if mode == "vertex" else g.m)
    if n0 <= 0:
        table.source = "static:empty-graph"
        return table

    # ---- pilot: one cheap jnp expand of a size-1 seed chunk ------------
    # Its children give every later probe a REALISTIC frontier: multi-
    # vertex members exercise the kernels' dedup/validity lanes that an
    # all-valid size-1 chunk skips — probing on size-1 rows picks Pallas
    # on workloads where jnp wins the real supersteps.
    rows = min(PROBE_CHUNK_ROWS, n0, max(int(config.chunk_size), 1))
    members = jnp.arange(rows, dtype=jnp.int32)[:, None]
    n_valid = jnp.ones((rows,), jnp.int32)
    out_cap = min(
        PROBE_OUT_CAP,
        1 << max(0, (rows * max(int(g.max_degree), 1) - 1).bit_length()),
    )

    def expand_probe(up, ck, m=members, nv=n_valid, cap=out_cap):
        return explore.expand_and_compact(
            g, m, nv, mode, cap,
            use_pallas=up, fused=False, interpret=interpret,
            compact_kernel=ck,
        )

    children, count = expand_probe(False, False)[:2]
    childk = children.shape[1]
    n_children = int(count)

    # ---- probe 1: expand ladder -> use_pallas, compact_kernel ----------
    if n_children >= 8:
        lrows = min(n_children, out_cap, PROBE_CHUNK_ROWS)
        lm = children[:lrows]
        lnv = jnp.full((lrows,), childk, jnp.int32)
        lcap = min(
            PROBE_OUT_CAP,
            1 << max(0, (lrows * max(int(g.max_degree), 1) - 1).bit_length()),
        )
    else:                       # degenerate graph: fall back to the seed
        lm, lnv, lcap = members, n_valid, out_cap

    ladder = [("jnp", False, False), ("pallas", True, False),
              ("pallas+compact", True, True), ("jnp+compact", False, True)]
    best_name, best_us = None, float("inf")
    for name, up, ck in ladder:
        us = _time_us(
            lambda up=up, ck=ck: expand_probe(up, ck, lm, lnv, lcap)
        )
        timings[f"expand.{name}"] = round(us, 1)
        if us < best_us:
            best_name, best_us = (up, ck), us
    # hysteresis: a kernel combo must beat plain jnp by a clear margin to
    # displace it — probe argmins between near-tied candidates are noise,
    # and a noise-picked combo can measure slower at real frontier sizes.
    if best_us >= EXPAND_HYSTERESIS * timings["expand.jnp"]:
        best_name = (False, False)
    table.use_pallas, table.compact_kernel = best_name

    if not app.wants_patterns:
        # nothing to aggregate: placement knobs are moot, and the fused
        # pipeline's only per-chunk cost is the device-resident count
        # buffer — strictly cheaper than the legacy loop's per-chunk sync.
        table.async_chunks = True
        return table

    # ---- pilot children -> real quick codes for the bin probes ---------
    nv_children = jnp.where(
        jnp.arange(out_cap) < jnp.minimum(count, out_cap), childk, 0
    ).astype(jnp.int32)
    qp = programs.quick_patterns(g, mode, children, nv_children)
    codes, valid = qp.codes, nv_children > 0
    reps = -(-PROBE_BIN_ROWS // out_cap)
    codes_big = jnp.tile(codes, (reps, 1))[:PROBE_BIN_ROWS]
    valid_big = jnp.tile(valid, (reps,))[:PROBE_BIN_ROWS]
    jax.block_until_ready((codes_big, valid_big))
    cap = min(max(int(config.agg_qcap), 1), 4096)

    bin_fn = jax.jit(
        agg_kernel.bin_rows,
        static_argnums=(2,),
        static_argnames=("use_kernel", "block", "interpret", "method"),
    )

    # ---- probe 2: bin ladder -> aggregate_bin, aggregate_kernel --------
    cands = [("sort", False), ("radix", False)]
    if platform in COMPILED_BACKENDS:
        cands += [("sort", True), ("radix", True)]
    best_bin, best_bin_us = None, float("inf")
    for method, uk in cands:
        us = _time_us(lambda m=method, uk=uk: bin_fn(
            codes_big, valid_big, cap,
            use_kernel=uk, interpret=interpret, method=m,
        ))
        timings[f"bin.{method}{'.kernel' if uk else ''}"] = round(us, 1)
        if us < best_bin_us:
            best_bin, best_bin_us = (method, uk), us
    table.aggregate_bin, table.aggregate_kernel = best_bin

    # ---- probe 3: placement -> device_aggregate ------------------------
    # Device level 1 pays a per-chunk fold (bin over one chunk's children)
    # plus a weighted re-merge of the carried table; the host path pays one
    # per-superstep drain (transfer + numpy lexsort-unique over all rows).
    # Compare them per ROW — that is the unit both scale in.
    method, uk = best_bin
    fold_us = _time_us(lambda: bin_fn(
        codes_big[:out_cap], valid_big[:out_cap], cap,
        use_kernel=uk, interpret=interpret, method=method,
    ))
    n_merge = min(2 * cap, codes_big.shape[0])
    w = jnp.ones((n_merge,), jnp.int64)
    merge_us = _time_us(lambda: bin_fn(
        codes_big[:n_merge], valid_big[:n_merge], cap, w,
        use_kernel=uk, interpret=interpret, method=method,
    ))

    def host_probe():
        c = np.asarray(codes_big)
        v = np.asarray(valid_big)
        cc = c[v]
        if cc.size:
            np.unique(cc, axis=0)
        return ()

    host_us = _time_us(host_probe)
    device_per_row = (fold_us + merge_us) / max(out_cap, 1)
    host_per_row = host_us / max(PROBE_BIN_ROWS, 1)
    timings["place.device_fold"] = round(fold_us, 1)
    timings["place.device_merge"] = round(merge_us, 1)
    timings["place.host_drain"] = round(host_us, 1)
    timings["place.device_per_row"] = round(device_per_row, 4)
    timings["place.host_per_row"] = round(host_per_row, 4)
    table.device_aggregate = device_per_row < host_per_row

    # ---- probe 4: pipeline shape -> async_chunks -----------------------
    # Legacy chunk loop: every chunk pays a host sync, a host->device chunk
    # upload, and a separate quick-pattern pass.  Fused pipeline: chunks
    # stay device-resident; the per-chunk cost is the carried-partial fold
    # when aggregating on device, ~zero when the codes drain once.
    sync_us = _time_us(lambda: jax.device_get(count))
    host_members = np.asarray(members)
    upload_us = _time_us(lambda: jnp.asarray(host_members))
    qp_us = _time_us(lambda: programs.quick_patterns(
        g, mode, children, nv_children
    ))
    legacy_tax = sync_us + upload_us + qp_us
    fused_tax = (fold_us + merge_us) if table.device_aggregate else 0.0
    timings["async.sync"] = round(sync_us, 1)
    timings["async.upload"] = round(upload_us, 1)
    timings["async.quick_patterns"] = round(qp_us, 1)
    timings["async.legacy_chunk_tax"] = round(legacy_tax, 1)
    timings["async.fused_chunk_tax"] = round(fused_tax, 1)
    table.async_chunks = fused_tax <= legacy_tax

    # ---- probe 5: level-2 placement -> canonical_placement -------------
    # Device refine batches the whole distinct-code table through the
    # permutation kernel (upload + refine + drain, the real device-route
    # cost); the host batch is canon_math._canonicalize_batch per nv
    # group (memo-cold, exactly what a miss pays).  Device wins on raw
    # speed; otherwise prefer overlapping the host batch with the next
    # superstep (host_async) when the app's filters allow a deferred
    # table, else stay on the synchronous host reference.
    from repro.core import aggregation, canon_math
    from repro.kernels import canonical_refine

    u = np.unique(np.asarray(codes)[np.asarray(valid)], axis=0)
    if len(u):
        device_us = _time_us(lambda: canonical_refine.canonicalize_on_device(
            u, use_kernel=table.aggregate_kernel, interpret=interpret,
        ))

        def host_canon():
            by_nv: Dict[int, list] = {}
            for i in range(len(u)):
                by_nv.setdefault(int(u[i, 0]) & 0xF, []).append(i)
            for js in by_nv.values():
                canon_math._canonicalize_batch(u[js])
            return ()

        host_us = _time_us(host_canon)
        timings["canon.device"] = round(device_us, 1)
        timings["canon.host"] = round(host_us, 1)
        if device_us < host_us:
            table.canonical_placement = "device"
        elif table.device_aggregate and aggregation.async_level2_ok(app):
            # host_async only exists on the device-aggregation path (the
            # host reference has no deferrable table) — a host_async
            # decision with device_aggregate=False would silently run
            # synchronously, so don't record one
            table.canonical_placement = "host_async"
        else:
            table.canonical_placement = "host"
    return table


# ---------------------------------------------------------------------------
# resolution: the one entry point (ExecutionBackend.bind)
# ---------------------------------------------------------------------------

def resolve(config, g, app, backend_name: str):
    """Resolve every unset knob of ``config`` to a concrete choice.

    Returns ``(concrete_config, table)``: a config copy whose
    ``DECIDED_KNOBS`` are all concrete (the store/program builders never
    see a tri-state again), and the effective decision table (user
    overrides folded in) for ``RunStats``/trace recording."""
    mode = getattr(config, "cost_model", "auto")
    if mode not in COST_MODEL_MODES:
        raise ValueError(
            f"unknown cost_model {mode!r} (expected one of {COST_MODEL_MODES})"
        )
    if mode == "off":
        table = static_table(backend_name, source="forced:off")
    elif mode != "auto":
        table = forced_table(mode, backend_name)
    elif int(g.m) < int(config.cost_model_min_edges):
        table = static_table(backend_name)
    else:
        from repro.core.runtime import checkpoint

        key = cache_key(
            backend_name, _platform(),
            checkpoint.app_fingerprint(app), checkpoint.graph_fingerprint(g),
            config_signature(config),
        )
        table = _PROCESS_CACHE.get(key)
        if table is None and config.cost_model_dir:
            table = _load_cached(config.cost_model_dir, key)
        if table is None:
            table = calibrate(g, app, config, backend_name)
            if config.cost_model_dir and table.source == "calibrated":
                _save_cached(config.cost_model_dir, key, table)
        _PROCESS_CACHE[key] = table

    # explicit config knobs always win; the returned table reflects the
    # EFFECTIVE choices (overrides folded in) without poisoning the cache.
    table = table.copy()
    concrete = {}
    for knob in DECIDED_KNOBS:
        user = getattr(config, knob)
        if user is None:
            concrete[knob] = getattr(table, knob)
        else:
            concrete[knob] = user
            setattr(table, knob, user)
            table.timings[f"override.{knob}"] = 1
    return dataclasses.replace(config, **concrete), table
