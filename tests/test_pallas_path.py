"""The Pallas canonical-check kernel on the engine hot path.

Covers the dispatch layer (interpret auto-detection, VMEM graph-size
fallback), the batch-shape hardening of the kernel wrappers (empty and
non-power-of-two batches), the fused ``expand_canonical`` kernel against
the jnp expansion, and the acceptance-criterion equivalence: ``engine.run``
with ``use_pallas=True`` and ``False`` produce identical patterns for
motifs, cliques, and FSM on the seed graphs.

All kernel invocations pin ``interpret=True`` so CPU CI runs the exact
kernel dataflow deterministically.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import canonical, explore, graph as G, odag, to_device
from repro.core import run, EngineConfig
from repro.core.apps import CliquesApp, FSMApp, MotifsApp
from repro.kernels import dispatch
from repro.kernels.canonical_check import ops as cc_ops
from repro.kernels.canonical_check.canonical_check import canonical_check_pallas
from repro.kernels.canonical_check.ref import canonical_check_ref


def _random_batch(rng, n, k, b):
    members = np.full((b, k), -1, np.int32)
    n_valid = (
        rng.integers(1, k + 1, b).astype(np.int32) if b else np.zeros(0, np.int32)
    )
    for i in range(b):
        members[i, : n_valid[i]] = rng.choice(n, size=n_valid[i], replace=False)
    cand = (
        rng.integers(0, n, b).astype(np.int32) if b else np.zeros(0, np.int32)
    )
    return jnp.asarray(members), jnp.asarray(n_valid), jnp.asarray(cand)


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------

def test_resolve_interpret_explicit_passthrough():
    assert dispatch.resolve_interpret(True) is True
    assert dispatch.resolve_interpret(False) is False


def test_resolve_interpret_refuses_interpreter_on_tpu(monkeypatch):
    """On a TPU backend the kernels compile; asking for the interpreter is
    an error, not a silent slow path."""
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    assert dispatch.resolve_interpret(None) is False
    assert dispatch.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret"):
        dispatch.resolve_interpret(True)


def test_run_records_kernel_routes(monkeypatch):
    """RunStats.kernel_routes names each kernel a run traced and how it
    ran, including a VMEM-size decline to the jnp route."""
    g = G.random_labeled(30, 70, n_labels=2, seed=11)
    cfg = EngineConfig(use_pallas=True, compact_kernel=True,
                       pallas_interpret=True, max_steps=3)
    jax.clear_caches()
    res = run(g, MotifsApp(max_size=3), cfg)
    assert res.stats.kernel_routes["canonical_check"] == ["interpret"]
    assert res.stats.kernel_routes["prefix_sum"] == ["interpret"]
    monkeypatch.setattr(cc_ops, "VMEM_BITMAP_LIMIT", 0)
    jax.clear_caches()
    res = run(g, CliquesApp(max_size=3), cfg)
    assert res.stats.kernel_routes["canonical_check"] == [
        "jnp:bitmap-exceeds-vmem"
    ]


def test_resolve_interpret_auto_matches_backend():
    expected = jax.default_backend() not in dispatch.COMPILED_BACKENDS
    assert dispatch.resolve_interpret(None) is expected
    # the engine-level static default is stricter: kernels default on only
    # where they are validated (TPU); GPU/CPU default to the jnp path
    from repro.core.runtime.costmodel import static_table
    assert static_table("serial").use_pallas is (
        jax.default_backend() == "tpu"
    )


def test_large_graph_falls_back_to_jnp(monkeypatch):
    g = G.random_labeled(50, 120, n_labels=2, seed=5)
    dg = to_device(g)
    m, nv, c = _random_batch(np.random.default_rng(5), 50, 4, 64)
    want = np.asarray(canonical.vertex_check(dg, m, nv, c))
    # force the "bitmap too big for VMEM" branch
    monkeypatch.setattr(cc_ops, "VMEM_BITMAP_LIMIT", 0)
    assert not cc_ops.fits_vmem(dg)
    got = np.asarray(cc_ops.canonical_check(dg, m, nv, c, interpret=True))
    np.testing.assert_array_equal(got, want)


def test_edge_mode_routes_to_jnp_check():
    g = G.triangle_plus_tail()
    dg = to_device(g)
    members = jnp.asarray([[0, 2, -1], [1, -1, -1]], jnp.int32)
    nv = jnp.asarray([2, 1], jnp.int32)
    cand = jnp.asarray([3, 0], jnp.int32)
    got = cc_ops.canonical_check(dg, members, nv, cand, mode="edge", interpret=True)
    want = canonical.edge_check(dg, members, nv, cand)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# batch-shape hardening (satellite regression: b in {0, 1, 1023, 1025})
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b", [0, 1, 1023, 1025])
def test_canonical_check_batch_sizes(b):
    g = G.random_labeled(60, 150, n_labels=2, seed=b)
    dg = to_device(g)
    m, nv, c = _random_batch(np.random.default_rng(b), 60, 4, b)
    got = canonical_check_pallas(
        m, nv, c, dg.adj_bits, block_b=256, interpret=True
    )
    assert got.shape == (b,)
    want = canonical_check_ref(dg, m, nv, c)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b", [0, 1, 1023, 1025])
def test_ops_wrapper_batch_sizes(b):
    g = G.random_labeled(40, 90, n_labels=2, seed=b + 100)
    dg = to_device(g)
    m, nv, c = _random_batch(np.random.default_rng(b + 100), 40, 3, b)
    got = cc_ops.canonical_check(dg, m, nv, c, interpret=True)
    assert got.shape == (b,)
    want = canonical_check_ref(dg, m, nv, c)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_expand_canonical_empty_frontier():
    dg = to_device(G.triangle_plus_tail())
    cand, valid, keep = cc_ops.expand_canonical(
        dg, jnp.zeros((0, 3), jnp.int32), jnp.zeros((0,), jnp.int32),
        interpret=True,
    )
    assert cand.shape == (0, 3, dg.max_degree)
    assert valid.shape == keep.shape == cand.shape


# ---------------------------------------------------------------------------
# fused expansion kernel vs the jnp expansion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,n,m,k", [(0, 30, 70, 2), (1, 50, 140, 3)])
def test_fused_expand_matches_jnp(seed, n, m, k):
    g = G.random_labeled(n, m, n_labels=2, seed=seed)
    dg = to_device(g)
    # grow a real frontier of size-k canonical embeddings via the jnp path
    members = jnp.arange(dg.n, dtype=jnp.int32)[:, None]
    for size in range(1, k):
        nv = jnp.full((members.shape[0],), size, jnp.int32)
        exp = explore.expand_vertex(dg, members, nv)
        children, count = explore.compact(members, exp, exp.keep, 1 << 14)
        members = children[: int(count)]
    nv = jnp.full((members.shape[0],), k, jnp.int32)

    e_jnp = explore.expand_vertex(dg, members, nv)
    e_fused = explore.expand_vertex(
        dg, members, nv, use_pallas=True, fused=True, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(e_jnp.rows), np.asarray(e_fused.rows))
    np.testing.assert_array_equal(np.asarray(e_jnp.cand), np.asarray(e_fused.cand))
    np.testing.assert_array_equal(np.asarray(e_jnp.keep), np.asarray(e_fused.keep))
    assert int(e_jnp.n_generated) == int(e_fused.n_generated)
    assert int(e_jnp.n_canonical) == int(e_fused.n_canonical)


def test_unfused_pallas_expand_matches_jnp():
    g = G.random_labeled(40, 100, n_labels=2, seed=7)
    dg = to_device(g)
    members = jnp.arange(dg.n, dtype=jnp.int32)[:, None]
    nv = jnp.ones((dg.n,), jnp.int32)
    e_jnp = explore.expand_vertex(dg, members, nv)
    e_pal = explore.expand_vertex(
        dg, members, nv, use_pallas=True, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(e_jnp.keep), np.asarray(e_pal.keep))


# ---------------------------------------------------------------------------
# acceptance criterion: engine equivalence for all three example apps
# ---------------------------------------------------------------------------

APPS = [
    ("motifs", lambda: MotifsApp(max_size=3)),
    ("cliques", lambda: CliquesApp(max_size=4)),
    ("fsm", lambda: FSMApp(support=3, max_size=3)),
]


@pytest.mark.parametrize("name,mk", APPS, ids=[a[0] for a in APPS])
def test_engine_pallas_equivalence(name, mk):
    g = G.random_labeled(60, 150, n_labels=3, seed=3)
    base = run(g, mk(), EngineConfig(use_pallas=False))
    pallas = run(
        g, mk(), EngineConfig(use_pallas=True, pallas_interpret=True)
    )
    assert base.patterns == pallas.patterns
    fused = run(
        g, mk(),
        EngineConfig(use_pallas=True, fused_expand=True, pallas_interpret=True),
    )
    assert base.patterns == fused.patterns


def test_engine_pallas_equivalence_paper_graph():
    g = G.paper_figure2()
    base = run(g, MotifsApp(max_size=3), EngineConfig(use_pallas=False))
    pallas = run(
        g, MotifsApp(max_size=3),
        EngineConfig(use_pallas=True, pallas_interpret=True),
    )
    assert base.patterns == pallas.patterns


def test_distributed_pallas_equivalence():
    """pallas_call inside the shard_map worker (needs check_vma=False:
    a pallas_call has no varying-axes rule)."""
    from repro.core.distributed import DistConfig, run_distributed

    mesh = jax.make_mesh((1,), ("data",))
    g = G.random_labeled(40, 90, n_labels=2, seed=1)
    ser = run(g, MotifsApp(max_size=3), EngineConfig(use_pallas=False))
    dist = run_distributed(
        g, MotifsApp(max_size=3), mesh,
        DistConfig(use_pallas=True, pallas_interpret=True),
    )
    assert ser.patterns == dist.patterns


# ---------------------------------------------------------------------------
# odag extraction through the kernel dispatch
# ---------------------------------------------------------------------------

def test_odag_extract_pallas_equivalence():
    g = G.random_labeled(40, 100, n_labels=2, seed=9)
    res = run(
        g, MotifsApp(max_size=3, collect_embeddings=True),
        EngineConfig(use_pallas=False),
    )
    emb = res.embeddings[3]
    dg = to_device(g)
    o = odag.build(emb)
    base = odag.extract(dg, o)
    pal = odag.extract(dg, o, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(base, pal)
