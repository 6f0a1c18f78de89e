"""Cost-model dispatch tests (DESIGN.md §14).

Covers the radix/bucket bin (`kernels/radix_bin.py`) against the
`lax.sort` bin and a numpy oracle — including empty, single-slot,
overflow, weighted and >63-bit wide-key inputs on both the jnp and the
Pallas routes — the forced-decision matrix (every `cost_model` mode
produces bit-identical results across apps × stores × backends), the
calibration-cache persistence/invalidation roundtrip, and the decision
table's observability contract (recorded in `RunStats`, explicit config
knobs override it).

Real calibration probes run once with shrunk probe sizes; the cache
tests stub `calibrate` so the roundtrip is fast and deterministic.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, RunConfig, graph as G, run, to_device
from repro.core.apps import CliquesApp, FSMApp, MotifsApp
from repro.core.runtime import costmodel, faults
from repro.kernels import radix_bin
from repro.kernels.aggregate import bin_rows


def _fake_codes(rng, b, nv=3, n_labels=4):
    """Synthetic quick codes honouring the encoding (words < 2^32)."""
    bits = rng.integers(0, 1 << 3, b).astype(np.int64)
    w0 = nv | (bits << 4)
    w1 = np.zeros(b, np.int64)
    labels = rng.integers(0, n_labels, (b, min(nv, 4)))
    for i in range(min(nv, 4)):
        w1 |= labels[:, i].astype(np.int64) << (8 * i)
    return np.stack([w0, w1, np.zeros(b, np.int64)], axis=1)


def _oracle(codes, valid, weights=None):
    """Numpy reference of the full bin_rows contract."""
    cc = codes[valid]
    if len(cc):
        ref_u, ref_inv = np.unique(cc, axis=0, return_inverse=True)
    else:
        ref_u = np.zeros((0, 3), np.int64)
        ref_inv = np.zeros((0,), np.int64)
    q = len(ref_u)
    w = weights[valid] if weights is not None else np.ones(len(cc), np.int64)
    counts = np.zeros(q, np.int64)
    np.add.at(counts, ref_inv, w)
    inv = np.full(len(codes), -1, np.int32)
    inv[valid] = ref_inv
    return ref_u, counts, inv, q


def _check_bin(codes, valid, cap, weights=None, **kw):
    """One bin call (sort vs radix vs oracle), exact on every output."""
    jw = None if weights is None else jnp.asarray(weights)
    got_s = bin_rows(jnp.asarray(codes), jnp.asarray(valid), cap, jw,
                     method="sort", **kw)
    got_r = bin_rows(jnp.asarray(codes), jnp.asarray(valid), cap, jw,
                     method="radix", **kw)
    ref_u, ref_c, ref_inv, q = _oracle(codes, valid, weights)
    for got, name in ((got_s, "sort"), (got_r, "radix")):
        u, c, inv, n, uv = (np.asarray(x) for x in got)
        assert int(n) == q, name                       # unclamped distinct
        k = min(q, cap)
        np.testing.assert_array_equal(u[:k], ref_u[:k], err_msg=name)
        np.testing.assert_array_equal(c[:k], ref_c[:k], err_msg=name)
        np.testing.assert_array_equal(inv, ref_inv, err_msg=name)
        np.testing.assert_array_equal(uv, np.arange(cap) < q, err_msg=name)
        assert (c[k:] == 0).all(), name                # pad slots are empty


# ---------------------------------------------------------------------------
# radix bin vs lax.sort bin vs numpy oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("b,cap,pv", [
    (1000, 1024, 0.9),     # ordinary batch, some invalid rows
    (500, 8, 1.0),         # overflow: far more distinct codes than cap
    (257, 64, 0.5),        # non-pow2 rows, half invalid
])
def test_radix_bin_matches_sort_and_oracle(use_kernel, b, cap, pv):
    rng = np.random.default_rng(b + cap)
    codes = _fake_codes(rng, b)
    valid = rng.random(b) < pv
    _check_bin(codes, valid, cap, use_kernel=use_kernel, interpret=True)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_radix_bin_single_slot_empty_all_invalid(use_kernel):
    kw = dict(use_kernel=use_kernel, interpret=True)
    one = np.tile(np.array([[3 | (5 << 4), 7, 0]], np.int64), (40, 1))
    _check_bin(one, np.ones(40, bool), 16, **kw)
    _check_bin(np.zeros((0, 3), np.int64), np.zeros((0,), bool), 16, **kw)
    rng = np.random.default_rng(9)
    _check_bin(_fake_codes(rng, 64), np.zeros(64, bool), 16, **kw)


def test_radix_bin_weighted_fold():
    rng = np.random.default_rng(2)
    codes = _fake_codes(rng, 300)
    w = rng.integers(1, 9, 300).astype(np.int64)
    valid = rng.random(300) < 0.8
    _check_bin(codes, valid, 512, weights=w)


def test_radix_bin_wide_keys_fall_back_exactly():
    """Words too wide to fuse into one 63-bit key: the in-program
    `lax.cond` slow path must still match the oracle bit for bit."""
    rng = np.random.default_rng(3)
    codes = _fake_codes(rng, 200)
    # widen all three words (still < 2^32 each) so the used bits sum > 63
    codes[:, 0] |= rng.integers(0, 1 << 30, 200).astype(np.int64) << 1
    codes[:, 1] |= rng.integers(0, 1 << 28, 200).astype(np.int64) << 3
    codes[:, 2] |= rng.integers(0, 1 << 28, 200).astype(np.int64) << 2
    valid = rng.random(200) < 0.9
    _check_bin(codes, valid, 64)    # with overflow
    _check_bin(codes, valid, 512)   # without


def test_radix_sort_codes_matches_sort_codes():
    from repro.kernels.aggregate import sort_codes

    rng = np.random.default_rng(4)
    codes = jnp.asarray(_fake_codes(rng, 500))
    valid = jnp.asarray(np.random.default_rng(5).random(500) < 0.7)
    sc, sv, _ = sort_codes(codes, valid)
    rc, rv, order = radix_bin.radix_sort_codes(
        codes, valid, block=128, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(rc), np.asarray(sc))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(sv))
    # order is a real permutation
    np.testing.assert_array_equal(np.sort(np.asarray(order)), np.arange(500))


def test_radix_sort_codes_multi_block():
    """Digit cursors carry across grid steps (3,000 rows, 1,024 a step)."""
    from repro.kernels.aggregate import sort_codes

    rng = np.random.default_rng(6)
    codes = jnp.asarray(_fake_codes(rng, 3000))
    valid = jnp.asarray(rng.random(3000) < 0.7)
    sc, sv, _ = sort_codes(codes, valid)
    rc, rv, _ = radix_bin.radix_sort_codes(codes, valid, block=1024,
                                           interpret=True)
    np.testing.assert_array_equal(np.asarray(rc), np.asarray(sc))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(sv))


# ---------------------------------------------------------------------------
# forced-decision matrix: bit-identical results across every table choice
# ---------------------------------------------------------------------------

_APPS = [
    ("motifs", lambda: MotifsApp(max_size=3)),
    ("cliques", lambda: CliquesApp(max_size=4)),
    ("fsm", lambda: FSMApp(support=2, max_size=3)),
]
_STORES = [
    ("raw", {}),
    ("odag", {"store": "odag"}),
    ("spill", {"device_budget_bytes": 1 << 14}),
]


def _result_key(res):
    """Everything a decision choice must NOT change: patterns and (for
    embedding apps) the exact embedding sets."""
    emb = {
        k: sorted(map(tuple, np.asarray(v).tolist()))
        for k, v in res.embeddings.items()
    }
    return (sorted(res.patterns.items()), emb)


@pytest.mark.parametrize("aname,mk", _APPS)
@pytest.mark.parametrize("sname,skw", _STORES)
def test_forced_modes_bit_identical_serial(aname, mk, sname, skw):
    g = G.random_labeled(40, 90, n_labels=2, seed=11)
    ref = run(g, mk(), EngineConfig(cost_model="off", **skw))
    for mode in ("force_device", "force_host"):
        got = run(g, mk(), EngineConfig(cost_model=mode, **skw))
        assert _result_key(got) == _result_key(ref), (aname, sname, mode)
        assert got.stats.cost_model["source"] == f"forced:{mode}"
    # auto on a tiny graph resolves statically — same results, no pilot
    auto = run(g, mk(), EngineConfig(**skw))
    assert _result_key(auto) == _result_key(ref)
    assert auto.stats.cost_model["source"] == "static"


@pytest.mark.parametrize("mode", ["auto", "force_device", "force_host"])
def test_forced_modes_bit_identical_shard_map(mode):
    from repro.core.distributed import DistConfig, run_distributed

    g = G.random_labeled(40, 90, n_labels=2, seed=12)
    mesh = jax.make_mesh((1,), ("data",))
    ref = run(g, MotifsApp(max_size=3), EngineConfig(cost_model="off"))
    got = run_distributed(
        g, MotifsApp(max_size=3), mesh, DistConfig(cost_model=mode)
    )
    assert got.patterns == ref.patterns
    src = got.stats.cost_model["source"]
    assert src == ("static" if mode == "auto" else f"forced:{mode}")


def test_forced_tables_pin_every_path():
    dev = costmodel.forced_table("force_device", "serial")
    host = costmodel.forced_table("force_host", "serial")
    assert dev.device_aggregate and dev.async_chunks
    assert dev.aggregate_bin == "radix"
    assert not host.device_aggregate and not host.async_chunks
    assert host.aggregate_bin == "sort"
    with pytest.raises(ValueError):
        costmodel.forced_table("force_nothing", "serial")
    with pytest.raises(ValueError):
        costmodel.resolve(
            EngineConfig(cost_model="bogus"),
            to_device(G.random_labeled(10, 20, n_labels=2, seed=0)),
            MotifsApp(max_size=3), "serial",
        )


def test_explicit_knobs_override_table():
    """User-set knobs always win over the table, and the effective table
    reflects the override (observability contract)."""
    g = to_device(G.random_labeled(40, 90, n_labels=2, seed=13))
    cfg = EngineConfig(cost_model="force_device", device_aggregate=False,
                       aggregate_bin="sort")
    resolved, table = costmodel.resolve(cfg, g, MotifsApp(max_size=3), "serial")
    assert resolved.device_aggregate is False
    assert resolved.aggregate_bin == "sort"
    assert table.device_aggregate is False
    assert table.aggregate_bin == "sort"
    assert "override.device_aggregate" in table.timings
    # non-overridden knobs still come from the forced table
    assert resolved.async_chunks is True


def test_decisions_recorded_in_runstats():
    g = G.random_labeled(40, 90, n_labels=2, seed=14)
    r = run(g, MotifsApp(max_size=3), EngineConfig())
    cm = r.stats.cost_model
    for knob in costmodel.DECIDED_KNOBS:
        assert knob in cm and cm[knob] is not None
    assert cm["backend"] == "serial"
    assert cm["platform"] == jax.default_backend()


# ---------------------------------------------------------------------------
# real calibration (shrunk probes) + the cache roundtrip
# ---------------------------------------------------------------------------

def _cal_graph(seed=15):
    return G.random_labeled(120, 600, n_labels=2, seed=seed)


def test_calibration_runs_and_resolves(monkeypatch):
    """A real probe pass: every decided knob concrete, timings populated,
    and the auto run bit-identical to the static config."""
    monkeypatch.setattr(costmodel, "PROBE_CHUNK_ROWS", 32)
    monkeypatch.setattr(costmodel, "PROBE_BIN_ROWS", 2048)
    monkeypatch.setattr(costmodel, "PROBE_OUT_CAP", 1 << 10)
    g = _cal_graph()
    costmodel.clear_cache()
    cfg = EngineConfig(cost_model_min_edges=100)
    ref = run(g, MotifsApp(max_size=3),
              dataclasses.replace(cfg, cost_model="off"))
    auto = run(g, MotifsApp(max_size=3), cfg)
    assert auto.patterns == ref.patterns
    cm = auto.stats.cost_model
    assert cm["source"] == "calibrated", cm
    assert any(k.startswith("expand.") for k in cm["timings"])
    assert any(k.startswith("bin.") for k in cm["timings"])
    for knob in costmodel.DECIDED_KNOBS:
        assert cm[knob] is not None
    # second run in the same process hits the process cache: no re-pilot
    again = run(g, MotifsApp(max_size=3), cfg)
    assert again.stats.cost_model["source"] == "calibrated"
    assert again.patterns == ref.patterns
    costmodel.clear_cache()


def _stub_calibrate(monkeypatch, marker):
    calls = []

    def fake(g, app, config, backend_name):
        calls.append(1)
        t = costmodel.static_table(backend_name, source="calibrated")
        t.timings["stub"] = marker
        return t

    monkeypatch.setattr(costmodel, "calibrate", fake)
    return calls


def test_cache_persistence_roundtrip(tmp_path, monkeypatch):
    """Disk cache: first resolve calibrates and persists; a fresh process
    (simulated by clearing the in-memory cache) loads the table back as
    source="cached" without re-piloting; a graph or config change
    re-pilots."""
    calls = _stub_calibrate(monkeypatch, 42.0)
    g = to_device(_cal_graph(16))
    app = MotifsApp(max_size=3)
    cfg = EngineConfig(cost_model_dir=str(tmp_path), cost_model_min_edges=0)
    costmodel.clear_cache()

    _, t1 = costmodel.resolve(cfg, g, app, "serial")
    assert t1.source == "calibrated" and len(calls) == 1
    assert len(list(tmp_path.glob("costmodel-*.json"))) == 1

    # same key, same process: cache hit, no new pilot
    _, t2 = costmodel.resolve(cfg, g, app, "serial")
    assert len(calls) == 1 and t2.timings["stub"] == 42.0

    # simulate a fresh process: in-memory cache cleared, disk survives
    costmodel.clear_cache()
    _, t3 = costmodel.resolve(cfg, g, app, "serial")
    assert t3.source == "cached" and len(calls) == 1
    assert t3.timings["stub"] == 42.0

    # a different graph re-pilots (new fingerprint, new file)
    costmodel.clear_cache()
    g2 = to_device(_cal_graph(17))
    _, t4 = costmodel.resolve(cfg, g2, app, "serial")
    assert t4.source == "calibrated" and len(calls) == 2
    assert len(list(tmp_path.glob("costmodel-*.json"))) == 2

    # a measurement-relevant config change re-pilots too
    costmodel.clear_cache()
    cfg2 = dataclasses.replace(cfg, chunk_size=cfg.chunk_size * 2)
    _, t5 = costmodel.resolve(cfg2, g, app, "serial")
    assert t5.source == "calibrated" and len(calls) == 3
    costmodel.clear_cache()


def test_cache_rejects_stale_schema(tmp_path, monkeypatch):
    calls = _stub_calibrate(monkeypatch, 7.0)
    g = to_device(_cal_graph(18))
    app = MotifsApp(max_size=3)
    cfg = EngineConfig(cost_model_dir=str(tmp_path), cost_model_min_edges=0)
    costmodel.clear_cache()
    costmodel.resolve(cfg, g, app, "serial")
    (path,) = tmp_path.glob("costmodel-*.json")
    d = json.loads(path.read_text())
    d["schema"] = -1
    path.write_text(json.dumps(d))
    costmodel.clear_cache()
    _, t = costmodel.resolve(cfg, g, app, "serial")
    assert t.source == "calibrated" and len(calls) == 2
    costmodel.clear_cache()


def test_small_graph_skips_pilot(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("pilot must not run below cost_model_min_edges")

    monkeypatch.setattr(costmodel, "calibrate", boom)
    g = to_device(G.random_labeled(20, 40, n_labels=2, seed=19))
    _, t = costmodel.resolve(
        EngineConfig(), g, MotifsApp(max_size=3), "serial"
    )
    assert t.source == "static"


def test_probe_failure_falls_back_static(monkeypatch):
    """A failing probe propagates out of ``resolve`` (no static fallback
    table hides it) and leaves nothing cached."""
    monkeypatch.setattr(
        costmodel, "_time_us",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("probe boom")),
    )
    g = to_device(_cal_graph(20))
    costmodel.clear_cache()
    with pytest.raises(RuntimeError, match="probe boom"):
        costmodel.resolve(
            EngineConfig(cost_model_min_edges=0), g, MotifsApp(max_size=3),
            "serial",
        )
    assert not costmodel._PROCESS_CACHE
    costmodel.clear_cache()


def test_degradation_ladder_handles_tristate_and_radix():
    """The faults ladder downshifts an unresolved (None) knob and turns
    the radix bin off before dropping device aggregation."""
    cfg = RunConfig(aggregate_bin="radix")
    cfg2, event = faults.apply_degradation(cfg, "aggregate", "crash")
    assert event == "radix_bin_off" and cfg2.aggregate_bin == "sort"
    cfg3, event = faults.apply_degradation(cfg2, "aggregate", "crash")
    assert event == "host_aggregate" and cfg3.device_aggregate is False
    cfg4, event = faults.apply_degradation(cfg, "expand", "crash")
    assert event == "fused_off" and cfg4.async_chunks is False
