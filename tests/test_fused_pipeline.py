"""Fused superstep pipeline tests (DESIGN.md §8).

Covers the stream-compaction kernel against its jnp contract, the
acceptance-criterion equivalence — ``async_chunks=True`` (fused) vs
``False`` (the PR-2 chunk loop) produce identical pattern dicts and
embedding *sets* for motifs, cliques, and FSM across all three frontier
stores — the O(1)-syncs-per-superstep property, the pow2 bucketing bound
on compiled chunk programs, the lazy device-array store append, and the
fused program under ``shard_map``.

Kernel invocations pin ``interpret=True`` so CPU CI runs the exact kernel
dataflow deterministically. Graphs stay ~40 vertices (engine runs are
seconds each; equivalence matrices multiply fast).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, graph as G, run, to_device
from repro.core.apps import CliquesApp, FSMApp, MotifsApp
from repro.core.store import RawStore
from repro.kernels.compact import stream_compact_pallas, stream_compact_ref


def _emb_sets(res):
    return {k: set(map(tuple, v.tolist())) for k, v in res.embeddings.items()}


# ---------------------------------------------------------------------------
# stream-compaction kernel vs the jnp contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b", [0, 1, 5, 127, 256, 1000])
@pytest.mark.parametrize("out_cap", [1, 64, 2048])
def test_stream_compact_matches_ref(b, out_cap):
    rng = np.random.default_rng(b + out_cap)
    keep = jnp.asarray(rng.random(b) < 0.3) if b else jnp.zeros((0,), bool)
    idx_k, cnt_k = stream_compact_pallas(keep, out_cap, block=64, interpret=True)
    idx_r, cnt_r = stream_compact_ref(keep, out_cap)
    # count is the UNCLAMPED kept total (host overflow detection relies on
    # it), identical between kernel and jnp route
    assert int(cnt_k) == int(cnt_r) == int(np.asarray(keep).sum())
    valid = min(int(cnt_k), out_cap)
    np.testing.assert_array_equal(
        np.asarray(idx_k[:valid]), np.asarray(idx_r[:valid])
    )
    # pad slots hold the jnp fill value (0)
    assert (np.asarray(idx_k[valid:]) == 0).all()


@pytest.mark.parametrize("b,block", [(5000, 1024), (70000, 8192)])
def test_prefix_sum_carries_across_blocks(b, block):
    """The scan's running total crosses grid steps exactly."""
    from repro.kernels.compact import prefix_sum_pallas

    keep = np.random.default_rng(b).random(b) < 0.4
    incl, total = prefix_sum_pallas(jnp.asarray(keep), block=block,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(incl), np.cumsum(keep))
    assert int(total) == int(keep.sum())


@pytest.mark.parametrize("keep", [
    np.zeros(100, bool),          # nothing kept
    np.ones(100, bool),           # everything kept
    np.arange(100) % 2 == 0,      # alternating
])
def test_stream_compact_edge_masks(keep):
    idx_k, cnt_k = stream_compact_pallas(
        jnp.asarray(keep), 128, block=32, interpret=True
    )
    idx_r, cnt_r = stream_compact_ref(jnp.asarray(keep), 128)
    assert int(cnt_k) == int(cnt_r)
    np.testing.assert_array_equal(np.asarray(idx_k), np.asarray(idx_r))


def test_compact_routes_through_kernel():
    """explore.compact(use_kernel=True) reproduces the jnp gather exactly
    on a real expansion."""
    from repro.core import explore

    dg = to_device(G.random_labeled(40, 90, n_labels=2, seed=1))
    members = jnp.arange(dg.n, dtype=jnp.int32)[:, None]
    nv = jnp.ones((dg.n,), jnp.int32)
    exp = explore.expand_vertex(dg, members, nv)
    c_ref, n_ref = explore.compact(members, exp, exp.keep, 256)
    c_ker, n_ker = explore.compact(
        members, exp, exp.keep, 256, use_kernel=True, interpret=True
    )
    assert int(n_ref) == int(n_ker)
    np.testing.assert_array_equal(np.asarray(c_ref), np.asarray(c_ker))


# ---------------------------------------------------------------------------
# acceptance criterion: fused == legacy for all apps x all three stores
# ---------------------------------------------------------------------------

APPS = [
    ("motifs", lambda: MotifsApp(max_size=3, collect_embeddings=True)),
    ("cliques", lambda: CliquesApp(max_size=4, collect_embeddings=True)),
    ("fsm", lambda: FSMApp(support=3, max_size=3, collect_embeddings=True)),
]
STORES = [
    ("raw", dict(store="raw")),
    ("odag", dict(store="odag")),
    ("spill", dict(store="raw", device_budget_bytes=2048)),
]
# small chunks so the fused pipeline actually exercises multi-chunk dispatch
SMALL = dict(chunk_size=64, initial_capacity=64)


@pytest.mark.parametrize("sname,skw", STORES, ids=[s[0] for s in STORES])
@pytest.mark.parametrize("aname,mk", APPS, ids=[a[0] for a in APPS])
def test_fused_matches_legacy(aname, mk, sname, skw):
    g = G.random_labeled(40, 90, n_labels=3, seed=3)
    legacy = run(g, mk(), EngineConfig(async_chunks=False, **SMALL, **skw))
    fused = run(g, mk(), EngineConfig(async_chunks=True, **SMALL, **skw))
    assert legacy.patterns == fused.patterns
    assert _emb_sets(legacy) == _emb_sets(fused)


def test_fused_with_compact_kernel_matches_legacy():
    g = G.random_labeled(40, 90, n_labels=3, seed=5)
    legacy = run(g, MotifsApp(max_size=3), EngineConfig(async_chunks=False))
    fused = run(
        g, MotifsApp(max_size=3),
        EngineConfig(
            async_chunks=True, compact_kernel=True, pallas_interpret=True,
            **SMALL,
        ),
    )
    assert legacy.patterns == fused.patterns


def test_fused_with_pallas_canonicality_matches_legacy():
    """The full kernel stack at once: fused expand_canonical + stream
    compaction inside the fused pipeline."""
    g = G.random_labeled(40, 90, n_labels=3, seed=7)
    legacy = run(g, MotifsApp(max_size=3), EngineConfig(async_chunks=False))
    fused = run(
        g, MotifsApp(max_size=3),
        EngineConfig(
            async_chunks=True, use_pallas=True, fused_expand=True,
            compact_kernel=True, pallas_interpret=True,
        ),
    )
    assert legacy.patterns == fused.patterns


# ---------------------------------------------------------------------------
# sync and compile accounting
# ---------------------------------------------------------------------------

def test_fused_syncs_are_constant_per_step():
    """The tentpole property: host control syncs per superstep are O(1) in
    the fused pipeline vs O(chunks) in the PR-2 loop."""
    g = G.random_labeled(40, 120, n_labels=2, seed=11)
    # packed chunks take rows up to a lane budget of chunk_size * k * D:
    # 8 rows' worth cuts this graph's frontiers into several chunks
    cfg = dict(SMALL, chunk_size=8)
    legacy = run(
        g, MotifsApp(max_size=3), EngineConfig(async_chunks=False, **cfg)
    )
    fused = run(
        g, MotifsApp(max_size=3), EngineConfig(async_chunks=True, **cfg)
    )
    assert legacy.patterns == fused.patterns
    for st in fused.stats.steps:
        assert st.n_host_syncs <= 2          # pilot + one drain per superstep
    exp_steps = [s for s in legacy.stats.steps if s.n_chunks > 1]
    assert exp_steps, "graph too small: legacy never ran multi-chunk"
    for st in exp_steps:
        assert st.n_host_syncs >= st.n_chunks   # one sync per chunk (PR-2)


def test_fused_spill_drains_per_wave():
    """With a device budget the fused pipeline drains one budget wave at a
    time (SpillStore's one-resident-wave contract): syncs scale with waves,
    not chunks, and results still match the unbudgeted run."""
    g = G.random_labeled(40, 120, n_labels=2, seed=29)
    base = run(g, MotifsApp(max_size=3), EngineConfig(async_chunks=False))
    budget = 16 * 4 * 3                 # a handful of rows per wave
    res = run(
        g, MotifsApp(max_size=3),
        EngineConfig(
            async_chunks=True, device_budget_bytes=budget,
            chunk_size=8, initial_capacity=32,
        ),
    )
    assert res.patterns == base.patterns
    for st in res.stats.steps:
        if st.n_chunks > 1:
            # <= 2 syncs per wave, and chunks strictly outnumber waves at
            # chunk_size 8 vs 16-row waves
            assert st.n_host_syncs < 2 * st.n_chunks


def test_pow2_bucketing_bounds_compiles():
    """Every dispatched chunk program signature is a (pow2 width, pow2
    capacity) pair and the jit cache grows by at most one entry per
    distinct signature — the recompile bound of DESIGN.md §8."""
    g = G.random_labeled(40, 120, n_labels=2, seed=13)
    res = run(
        g, MotifsApp(max_size=4), EngineConfig(async_chunks=True, **SMALL)
    )
    sigs = res.stats.chunk_signatures
    assert sigs, "no chunk programs dispatched"
    for size, width, lanes, cap in sigs:
        assert width & (width - 1) == 0, f"non-pow2 chunk width {width}"
        assert cap & (cap - 1) == 0, f"non-pow2 capacity {cap}"
        # packed lane buckets: pow2, or the lane budget of a full chunk
        budget = SMALL["chunk_size"] * size * int(g.degrees().max())
        assert lanes & (lanes - 1) == 0 or lanes == budget, lanes
    assert res.stats.n_compiles <= len(sigs)
    # the signature space itself is logarithmic: widths, lane buckets and
    # caps are pow2 buckets, so a frontier of any size compiles O(log)
    # widths and lane buckets per size
    assert len(sigs) <= 4 * len(res.stats.steps) + 4
    for size in {s[0] for s in sigs}:
        mine = [s for s in sigs if s[0] == size]
        widths, lane_buckets = {s[1] for s in mine}, {s[2] for s in mine}
        assert len(widths) <= max(widths).bit_length()
        assert len(lane_buckets) <= max(lane_buckets).bit_length() + 1


def test_chunk_program_cache_reused_across_runs():
    """A second run with an equal app config re-traces nothing."""
    g = G.random_labeled(40, 90, n_labels=2, seed=17)
    cfg = dict(async_chunks=True, chunk_size=32, initial_capacity=32)
    run(g, MotifsApp(max_size=3), EngineConfig(**cfg))
    again = run(g, MotifsApp(max_size=3), EngineConfig(**cfg))
    assert again.stats.n_compiles == 0


# ---------------------------------------------------------------------------
# lazy device-array store append
# ---------------------------------------------------------------------------

def test_raw_store_lazy_device_append():
    s = RawStore()
    padded = jnp.asarray(
        np.array([[0, 1], [2, 3], [-1, -1], [-1, -1]], np.int32)
    )
    s.append(padded, count=2)                 # device array, no transfer yet
    s.append(np.array([[4, 5]], np.int32))    # host block, no count
    s.seal(2)
    np.testing.assert_array_equal(
        s.materialize(), np.array([[0, 1], [2, 3], [4, 5]], np.int32)
    )
    assert s.n_rows == 3


def test_raw_store_append_count_zero_is_dropped():
    s = RawStore()
    s.append(jnp.zeros((4, 2), jnp.int32), count=0)
    s.seal(2)
    assert s.n_rows == 0


def test_odag_store_lazy_device_append():
    from repro.core.store import ODAGStore

    g = to_device(G.triangle_plus_tail())
    s = ODAGStore(g, mode="vertex")
    rows = np.array([[0, 1], [0, 2], [1, 2]], np.int32)
    padded = np.concatenate([rows, np.full((2, 2), -1, np.int32)])
    s.append(jnp.asarray(padded), count=3)
    s.seal(2)
    assert s.n_rows == 3
    got = {tuple(r) for r in s.materialize().tolist()}
    assert {tuple(r) for r in rows.tolist()} <= got


# ---------------------------------------------------------------------------
# the fused program under shard_map
# ---------------------------------------------------------------------------

def test_distributed_fused_matches_serial():
    from repro.core.distributed import DistConfig, run_distributed

    mesh = jax.make_mesh((1,), ("data",))
    g = G.random_labeled(40, 90, n_labels=3, seed=19)
    ser = run(g, MotifsApp(max_size=3), EngineConfig(async_chunks=False))
    for store in ("raw", "odag"):
        dist = run_distributed(
            g, MotifsApp(max_size=3), mesh,
            DistConfig(store=store, async_chunks=True),
        )
        assert ser.patterns == dist.patterns
        for st in dist.stats.steps:
            assert st.n_host_syncs <= 2      # one drain (+1 capacity retry)


def test_distributed_fused_fsm_carried_codes():
    """Edge-mode carried codes: FSM's alpha filter consumes codes emitted
    by the previous superstep's sharded expand."""
    from repro.core.distributed import DistConfig, run_distributed

    mesh = jax.make_mesh((1,), ("data",))
    g = G.random_labeled(40, 90, n_labels=3, seed=23)
    ser = run(g, FSMApp(support=3, max_size=3), EngineConfig(async_chunks=False))
    dist = run_distributed(
        g, FSMApp(support=3, max_size=3), mesh, DistConfig(async_chunks=True)
    )
    assert ser.patterns == dist.patterns
