"""Packed candidate lanes of the serial vertex-mode chunk program
(DESIGN.md §8): a row's lanes are the neighbour slots of its members,
``sum(deg(members))``, instead of the padded ``k * D``.

Covers bit-identity of the packed chunk program with the padded one
(children, counts, carried codes, level-1 partials, ``n_generated``,
``n_canonical``) on power-law graphs, zero-degree vertices, rows that fill
``k * D`` lanes, rows with ``n_valid < k``, trailing invalid lanes and
empty chunks; engine-level identity of the results in store order for both
chunk loops; the planner's lane budget and row order; and which graphs and
modes take the packed path at all.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Graph, graph as G, to_device
from repro.core import explore
from repro.core.apps import CliquesApp, FSMApp, MotifsApp
from repro.core.runtime import RunConfig, SerialBackend, SuperstepRuntime
from repro.core.runtime import programs
from repro.core.runtime.config import next_pow2


class _CollectedMotifs(MotifsApp):
    collect_embeddings = True


APPS = {"motifs": lambda: _CollectedMotifs(max_size=3),
        "cliques": lambda: CliquesApp(max_size=4)}
OUTPUTS = {"plain": {}, "patterns": {"with_patterns": True},
           "aggregates": {"with_aggregates": True}}


def _frontier(dg, size):
    """The canonical vertex-mode frontier of ``size`` (padded expansion)."""
    rows = np.arange(dg.n, dtype=np.int32)[:, None]
    for s in range(1, size):
        cap = next_pow2(len(rows) * s * dg.max_degree)
        ch, cnt, _, _ = explore.expand_and_compact(
            dg, jnp.asarray(rows), jnp.full((len(rows),), s, jnp.int32),
            "vertex", cap)
        rows = np.asarray(ch[: int(cnt)])
    return rows


def _complete(n):
    edges = [(u, v) for u in range(n) for v in range(u + 1, n)]
    return Graph(n=n, labels=np.arange(n) % 2, edges=np.array(edges))


def _isolated():
    """20 wired vertices and 10 of degree 0."""
    base = G.random_labeled(20, 60, n_labels=2, seed=5)
    return Graph(n=30, labels=np.arange(30) % 3, edges=base.edges)


def _case(name):
    """(device graph, members, n_valid, n_lanes) of one chunk."""
    if name == "power_law":
        dg = to_device(G.random_labeled(40, 140, n_labels=3, seed=3))
        rows = _frontier(dg, 2)[:37]
        nv = np.full(len(rows), 2, np.int32)
    elif name == "zero_degree":
        dg = to_device(_isolated())
        rows = np.arange(dg.n, dtype=np.int32)[:, None]
        nv = np.ones(len(rows), np.int32)
    elif name == "full_row":
        # every member of degree D: a row needs all of k * D lanes
        dg = to_device(_complete(6))
        rows = _frontier(dg, 3)[:1]
        nv = np.full(1, 3, np.int32)
    elif name == "short_rows":
        dg = to_device(G.random_labeled(40, 140, n_labels=3, seed=4))
        rows = _frontier(dg, 3)[:24]
        nv = np.random.default_rng(0).integers(0, 4, len(rows)).astype(
            np.int32)
    elif name == "trailing_lanes":
        dg = to_device(G.random_labeled(40, 140, n_labels=3, seed=6))
        rows = _frontier(dg, 2)[:9]
        nv = np.full(len(rows), 2, np.int32)
    else:                                   # empty: no valid member at all
        dg = to_device(G.random_labeled(40, 140, n_labels=3, seed=7))
        rows = np.full((8, 2), -1, np.int32)
        nv = np.zeros(8, np.int32)
    deg = np.asarray(dg.deg)
    used = int(sum(deg[r[:n]].sum() for r, n in zip(rows, nv)))
    lanes = next_pow2(max(used, 1))
    if name == "trailing_lanes":
        lanes *= 4
    if name == "full_row":                  # lanes exactly full, as a budget
        assert used == rows.shape[1] * dg.max_degree
        lanes = used
    return dg, jnp.asarray(rows), jnp.asarray(nv), lanes


@pytest.mark.parametrize("case", ["power_law", "zero_degree", "full_row",
                                  "short_rows", "trailing_lanes", "empty"])
@pytest.mark.parametrize("outputs", sorted(OUTPUTS))
@pytest.mark.parametrize("app_name", sorted(APPS))
def test_packed_chunk_program_matches_padded(app_name, outputs, case):
    dg, members, n_valid, lanes = _case(case)
    fn = programs.make_expand_fn(APPS[app_name](), "vertex", **OUTPUTS[outputs])
    for cap in (next_pow2(max(lanes, 1)), 4):       # roomy, and overflowing
        padded = fn(dg, members, n_valid, out_cap=cap)
        packed = fn(dg, members, n_valid, out_cap=cap, n_lanes=lanes)
        assert len(padded) == len(packed)
        for a, b in zip(padded, packed):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if case == "empty":
        assert int(packed[1]) == int(packed[-2]) == 0


def test_packed_canonical_check_kernel_matches_padded():
    """The packed lanes feed the Pallas canonicality kernel unchanged."""
    dg, members, n_valid, lanes = _case("power_law")
    fn = programs.make_expand_fn(MotifsApp(max_size=3), "vertex",
                                 use_pallas=True, interpret=True,
                                 with_patterns=True)
    cap = next_pow2(lanes)
    padded = fn(dg, members, n_valid, out_cap=cap)
    packed = fn(dg, members, n_valid, out_cap=cap, n_lanes=lanes)
    for a, b in zip(padded, packed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _mine(g, app_name, async_chunks, packed):
    rt = SuperstepRuntime(
        g, APPS[app_name](),
        RunConfig(async_chunks=async_chunks, chunk_size=16,
                  initial_capacity=64),
        SerialBackend(),
    )
    assert rt.backend._packed
    rt.backend._packed = packed
    return rt.run(), rt.backend.with_aggregates


@pytest.mark.parametrize("async_chunks", [True, False])
@pytest.mark.parametrize("app_name", sorted(APPS))
def test_engine_results_match_padded(app_name, async_chunks):
    g = G.mico_like(scale=0.0006)
    padded, partials = _mine(g, app_name, async_chunks, packed=False)
    packed, _ = _mine(g, app_name, async_chunks, packed=True)
    assert packed.patterns == padded.patterns
    assert packed.embeddings.keys() == padded.embeddings.keys()
    for size, rows in padded.embeddings.items():
        np.testing.assert_array_equal(packed.embeddings[size], rows)
    # level-1 partials fold per chunk: their merges' control reads (a few
    # bytes each) follow the chunk count, not the children
    same = ("n_generated", "n_canonical", "n_children", "rows_to_host") + (
        () if partials else ("bytes_to_host",))
    for a, b in zip(padded.stats.steps, packed.stats.steps):
        for k in same:
            assert getattr(a, k) == getattr(b, k), k
    lanes = [sum(s.n_lanes for s in r.stats.steps) for r in (padded, packed)]
    chunks = [sum(s.n_chunks for s in r.stats.steps) for r in (padded, packed)]
    assert lanes[1] < lanes[0] and chunks[1] < chunks[0]


@pytest.mark.parametrize("chunk_size", [1, 3, 16])
def test_planner_lane_budget_and_row_order(chunk_size):
    rng = np.random.default_rng(chunk_size)
    d, k = 9, 3
    deg = rng.integers(0, d + 1, 50)
    deg[7] = d
    wave = rng.integers(0, 50, (200, k)).astype(np.int32)
    wave[11] = 7                             # a row of k * D lanes
    budget = chunk_size * k * d
    plans = list(programs.plan_chunks(wave, chunk_size, d, deg))
    assert [p.lo for p in plans] == list(
        np.cumsum([0] + [p.rows for p in plans[:-1]]))
    assert sum(p.rows for p in plans) == len(wave)
    for p in plans:
        assert p.packed and p.rows >= 1
        assert p.used == deg[wave[p.lo: p.lo + p.rows]].sum()
        assert p.used <= p.lanes <= budget
        assert p.lanes == budget or p.lanes == next_pow2(max(p.used, 1))
        assert p.bucket == next_pow2(p.rows)
        # a chunk stops only where the next row would pass the budget
        nxt = p.lo + p.rows
        if nxt < len(wave):
            spent = np.maximum(deg[wave[p.lo: nxt + 1]].sum(axis=1), 1).sum()
            assert spent > budget
    if chunk_size == 1:
        (full,) = [p for p in plans if p.lo == 11]
        assert full.rows == 1 and full.used == full.lanes == budget


def test_planner_padded_cut_is_chunk_size_rows():
    wave = np.zeros((70, 2), np.int32)
    plans = list(programs.plan_chunks(wave, 32, 5))
    assert [(p.lo, p.rows, p.bucket, p.lanes, p.used, p.packed)
            for p in plans] == [(0, 32, 32, 320, 320, False),
                                (32, 32, 32, 320, 320, False),
                                (64, 6, 8, 80, 60, False)]


@pytest.mark.parametrize("setup,packed", [
    ("motifs", True),
    ("cliques", True),
    ("fsm", False),                  # edge mode
    ("partitioned", False),          # PartitionedGraph tile view
    ("fused_expand", False),         # the fused expand_canonical kernel
])
def test_which_runs_take_packed_lanes(setup, packed):
    g = G.random_labeled(24, 60, n_labels=2, seed=9)
    app = FSMApp(support=2, max_size=2) if setup == "fsm" else (
        CliquesApp(max_size=3) if setup == "cliques" else MotifsApp(max_size=2))
    cfg = RunConfig(chunk_size=8,
                    graph_partition=2 if setup == "partitioned" else 0,
                    fused_expand=setup == "fused_expand")
    rt = SuperstepRuntime(g, app, cfg, SerialBackend())
    assert rt.backend._packed is packed
    steps = rt.run().stats.steps
    d = rt.g.max_degree
    for s in steps:
        if not packed and s.n_chunks:
            k = s.size if app.mode == "vertex" else 2 * s.size
            assert s.n_lanes % (k * d) == 0
