"""Observability subsystem tests (DESIGN.md §12).

Covers the tracer contracts (span nesting/ordering, thread ids, the
disabled path staying a shared no-op), the metrics registry as the
single StepStats write path (``obs.count``/``set_stat`` bit-identical to
the raw ``st.x += v`` arithmetic, traced or not), the Chrome trace-event
export schema (every "X" event carries name/ph/ts/dur/pid/tid — the
subset Perfetto needs), phase coverage of real runs on both backends,
the ``trace=False`` zero-extra-syncs guard, the ``trace_sync`` probe
timings (``t_gather``/``t_exchange``) on partitioned runs, the
summary/phase-wall additions to ``RunStats``, every span reaching a
``jax.profiler`` capture (with the expansion/aggregation sub-spans nested
under their phases in both chunk loops), the always-on ``n_lanes`` /
``n_device_reads`` / ``rows_to_host`` counters, and ``obs.totals()``.

Graphs stay ~40 vertices: every engine run here is sub-second.
"""
import json
import threading

import numpy as np
import pytest

from benchmarks import render_trace
from repro.core import RunConfig, SuperstepRuntime, graph as G, obs
from repro.core.apps import CliquesApp, FSMApp, MotifsApp
from repro.core.obs import metrics as metrics_lib
from repro.core.obs import tracer as tracer_lib
from repro.core.stats import StepStats


def _graph():
    return G.random_labeled(40, 200, n_labels=3, seed=4)


APPS = {
    "motifs": lambda: MotifsApp(max_size=3),
    "cliques": lambda: CliquesApp(max_size=4),
    "fsm": lambda: FSMApp(support=3, max_size=3),
}

#: per-step counter stats that must be bit-identical traced vs untraced.
COUNTER_STATS = (
    "n_frontier", "n_children", "n_chunks", "n_host_syncs",
    "bytes_to_host", "collective_bytes", "n_generated", "n_canonical",
    "n_quick_patterns", "n_canonical_patterns", "n_iso_checks",
    "n_lanes", "n_device_reads", "rows_to_host",
)


# ---------------------------------------------------------------------------
# tracer: spans, nesting, ordering, disabled path
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering():
    tr = tracer_lib.Tracer()
    with tr.span("outer", step=1):
        with tr.span("inner_a"):
            pass
        with tr.span("inner_b"):
            pass
    # spans close innermost-first; outer closes last
    names = [sp.name for sp in tr.spans]
    assert names == ["inner_a", "inner_b", "outer"]
    outer = tr.spans[-1]
    a, b = tr.spans[0], tr.spans[1]
    assert a.parent == "outer" and b.parent == "outer"
    assert outer.parent is None
    assert a.depth == b.depth == 1 and outer.depth == 0
    # children fall inside the parent's [ts, ts+dur] window, in order
    assert outer.ts <= a.ts and a.ts + a.dur <= b.ts + 1e-6
    assert b.ts + b.dur <= outer.ts + outer.dur + 1e-6
    assert outer.args["step"] == 1


def test_span_threads_get_distinct_tids():
    tr = tracer_lib.Tracer()
    tracer_lib.install(tr)
    try:
        def work():
            with obs.span("worker"):
                pass
        t = threading.Thread(target=work)
        with obs.span("main"):
            t.start()
            t.join()
    finally:
        tracer_lib.install(None)
    tids = {sp.name: sp.tid for sp in tr.spans}
    assert tids["worker"] != tids["main"]
    # the thread's root span has no parent — stacks are per-thread
    worker = next(sp for sp in tr.spans if sp.name == "worker")
    assert worker.parent is None and worker.depth == 0


def test_disabled_span_is_shared_noop():
    assert tracer_lib.current() is None
    s1 = obs.span("anything", step=9)
    s2 = obs.span("else")
    assert s1 is s2  # one preallocated nullcontext, no per-call garbage
    with s1:
        pass


def test_fence_only_blocks_under_sync():
    import jax.numpy as jnp
    x = jnp.arange(8)
    obs.fence(x)                       # no tracer: must be a no-op
    tr = tracer_lib.Tracer(sync=False)
    tracer_lib.install(tr)
    try:
        obs.fence(x)
        assert tr.n_fences == 0
        assert not obs.sync_active()
    finally:
        tracer_lib.install(None)
    tr = tracer_lib.Tracer(sync=True)
    tracer_lib.install(tr)
    try:
        assert obs.sync_active()
        obs.fence(x, None)             # None leaves are tolerated
        assert tr.n_fences == 1
    finally:
        tracer_lib.install(None)


# ---------------------------------------------------------------------------
# metrics: the single write path
# ---------------------------------------------------------------------------

def test_count_and_set_stat_identical_arithmetic():
    a, b = StepStats(step=1, size=1), StepStats(step=1, size=1)
    reg = metrics_lib.MetricsRegistry()
    metrics_lib.install(reg)
    try:
        obs.count(a, "bytes_to_host", 123)
        obs.count(a, "bytes_to_host", np.int64(7))
        obs.set_stat(a, "n_generated", np.int32(55))
    finally:
        metrics_lib.install(None)
    b.bytes_to_host += 123
    b.bytes_to_host += np.int64(7)
    b.n_generated = np.int32(55)
    assert a.bytes_to_host == b.bytes_to_host
    assert a.n_generated == b.n_generated
    snap = reg.snapshot()
    assert snap["counters"]["bytes_to_host"] == 130
    assert snap["gauges"]["n_generated"] == 55
    # uninstalled: same arithmetic, no registry
    obs.count(a, "bytes_to_host", 1)
    assert a.bytes_to_host == 131


def test_gauge_and_device_memory_guarded():
    reg = metrics_lib.MetricsRegistry()
    metrics_lib.install(reg)
    try:
        obs.gauge("watermark", 10, step=1)
        obs.gauge("watermark", 30, step=2)
        obs.gauge("watermark", 20, step=3)
        mem = metrics_lib.sample_device_memory()   # None on CPU: no crash
        assert mem is None or mem > 0
    finally:
        metrics_lib.install(None)
    snap = reg.snapshot()
    assert snap["gauges"]["watermark"] == 20
    assert snap["gauge_max"]["watermark"] == 30


# ---------------------------------------------------------------------------
# Chrome trace export schema
# ---------------------------------------------------------------------------

def test_chrome_trace_schema_fields():
    tr = tracer_lib.Tracer()
    with tr.span("superstep", step=1):
        with tr.span("expand", step=1):
            pass
    tr.counter("bytes", to_host=10)
    events = obs.chrome_trace_events(tr)
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        for k in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert k in e, f"{e['name']}: missing {k}"
        assert e["dur"] >= 0
    cs = [e for e in events if e["ph"] == "C"]
    assert cs and cs[0]["args"] == {"to_host": 10}
    doc = {"traceEvents": events}
    assert obs.validate_chrome_trace(doc) == []
    # the validator actually rejects malformed docs
    assert obs.validate_chrome_trace({}) != []
    bad = {"traceEvents": [{"ph": "X", "name": "x", "ts": 0}]}
    assert any("dur" in p for p in obs.validate_chrome_trace(bad))


def test_phase_coverage_math():
    def x(name, ts, dur, parent=None):
        e = {"ph": "X", "name": name, "ts": ts, "dur": dur,
             "pid": 1, "tid": 0, "args": {}}
        if parent:
            e["args"]["parent"] = parent
        return e
    doc = {"traceEvents": [
        x("superstep", 0, 100),
        x("expand", 0, 60, "superstep"),
        x("aggregate", 60, 35, "superstep"),
        x("expand", 200, 999),            # wrong parent: not counted
    ]}
    cov = obs.phase_coverage(doc)
    assert cov["total_us"] == 100 and cov["covered_us"] == 95
    assert cov["coverage"] == pytest.approx(0.95)
    assert obs.phase_coverage({"traceEvents": []})["coverage"] == 1.0


# ---------------------------------------------------------------------------
# real runs: identity, zero extra syncs, coverage, both backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("store", ["raw", "odag", "spill"])
@pytest.mark.parametrize("app_name", ["motifs", "cliques", "fsm"])
def test_traced_run_bit_identical(app_name, store, tmp_path):
    g = _graph()
    kw = dict(store="raw", device_budget_bytes=4096) if store == "spill" \
        else dict(store=store)
    ref = SuperstepRuntime(g, APPS[app_name](), RunConfig(**kw)).run()
    traced = SuperstepRuntime(
        g, APPS[app_name](),
        RunConfig(trace=True, trace_dir=str(tmp_path), **kw),
    ).run()
    assert traced.patterns == ref.patterns
    assert ref.trace_path is None and traced.trace_path is not None
    assert len(ref.stats.steps) == len(traced.stats.steps)
    for a, b in zip(ref.stats.steps, traced.stats.steps):
        for k in COUNTER_STATS:
            assert getattr(a, k) == getattr(b, k), (app_name, store, k)
    # trace=False left no tracer behind; trace=True uninstalled after
    assert tracer_lib.current() is None
    assert metrics_lib.current() is None
    doc = json.load(open(traced.trace_path))
    assert obs.validate_chrome_trace(doc) == []
    # ≥0.90 here, not the acceptance gate's 0.95: these warm 40-vertex
    # runs finish supersteps in <1ms, where the fixed span bookkeeping
    # between phases is a visible fraction of the wall. The hard ≥95%
    # gate runs on the real mico_like workload (bench_obs + CI).
    assert obs.phase_coverage(doc)["coverage"] >= 0.90


@pytest.mark.parametrize("backend_kind", ["serial", "shard"])
def test_traced_run_coverage_both_backends(backend_kind, tmp_path):
    import jax
    from repro.core.runtime.shard import ShardMapBackend
    g = _graph()

    def backend():
        if backend_kind == "serial":
            return None
        return ShardMapBackend(jax.make_mesh((1,), ("data",)))

    ref = SuperstepRuntime(g, MotifsApp(max_size=3), RunConfig(),
                           backend()).run()
    traced = SuperstepRuntime(
        g, MotifsApp(max_size=3),
        RunConfig(trace=True, trace_dir=str(tmp_path)), backend(),
    ).run()
    assert traced.patterns == ref.patterns
    # zero extra host syncs from tracing, per step
    assert [s.n_host_syncs for s in traced.stats.steps] == \
        [s.n_host_syncs for s in ref.stats.steps]
    doc = json.load(open(traced.trace_path))
    assert obs.validate_chrome_trace(doc) == []
    # relaxed vs the 0.95 acceptance gate — see test_traced_run_bit_identical
    assert obs.phase_coverage(doc)["coverage"] >= 0.90


def test_trace_sync_probes_on_partitioned_runs(tmp_path):
    g = _graph()
    ref = SuperstepRuntime(g, MotifsApp(max_size=3), RunConfig()).run()
    cfg = RunConfig(trace=True, trace_dir=str(tmp_path), trace_sync=True,
                    graph_partition=2)
    res = SuperstepRuntime(g, MotifsApp(max_size=3), cfg).run()
    assert res.patterns == ref.patterns
    # the tile-gather probe charged t_gather on at least one superstep
    assert any(s.t_gather > 0 for s in res.stats.steps)
    # untraced / non-sync runs leave the probe timings at zero
    plain = SuperstepRuntime(
        g, MotifsApp(max_size=3), RunConfig(graph_partition=2)
    ).run()
    assert all(s.t_gather == 0 for s in plain.stats.steps)
    assert all(s.t_exchange == 0 for s in plain.stats.steps)


def test_trace_exports_jsonl_and_log(tmp_path, capsys):
    g = _graph()
    cfg = RunConfig(trace=True, trace_dir=str(tmp_path), log_every=1)
    res = SuperstepRuntime(g, MotifsApp(max_size=3), cfg).run()
    out = capsys.readouterr().out
    assert "[obs] step=1" in out and "bytes_to_host=" in out
    jsonl = res.trace_path.replace(".trace.json", ".events.jsonl")
    records = [json.loads(l) for l in open(jsonl)]
    kinds = {r["event"] for r in records}
    assert kinds == {"span", "superstep"}
    steps = [r for r in records if r["event"] == "superstep"]
    assert [r["step"] for r in steps] == [s.step for s in res.stats.steps]
    # otherData carries the run metadata + metrics snapshot
    doc = json.load(open(res.trace_path))
    other = doc["otherData"]
    assert other["backend"] == "serial"
    assert other["metrics"]["counters"]["n_host_syncs"] >= 1


def test_log_every_without_trace(tmp_path, capsys):
    g = _graph()
    res = SuperstepRuntime(
        g, MotifsApp(max_size=3), RunConfig(log_every=1)
    ).run()
    assert res.trace_path is None
    assert "[obs] step=1" in capsys.readouterr().out
    assert tracer_lib.current() is None


def test_observer_uninstalls_on_loop_exception(tmp_path):
    class Boom(MotifsApp):
        def filter(self, g, emb):
            raise RuntimeError("boom")
    g = _graph()
    cfg = RunConfig(trace=True, trace_dir=str(tmp_path))
    with pytest.raises(Exception):
        SuperstepRuntime(g, Boom(max_size=3), cfg).run()
    assert tracer_lib.current() is None
    assert metrics_lib.current() is None


def test_abort_path_flushes_partial_trace(tmp_path):
    """A run killed mid-superstep (§13 injected crash) still exports a
    well-formed partial trace: spans closed by the unwinding, document
    marked aborted, JSONL flushed with a terminal abort record, and
    ``render_trace --check`` accepts it (coverage gate waived — a partial
    superstep cannot meet it; schema validation still applies)."""
    from repro.core.runtime import FaultPlan, FaultSpec

    g = _graph()
    plan = FaultPlan([FaultSpec("expand", 2, "crash")])
    cfg = RunConfig(trace=True, trace_dir=str(tmp_path), faults=plan)
    rt = SuperstepRuntime(g, MotifsApp(max_size=3), cfg)
    with pytest.raises(Exception, match="injected"):
        rt.run()
    # tracer/registry uninstalled despite the abort
    assert tracer_lib.current() is None
    assert metrics_lib.current() is None
    # the partial Chrome trace landed, schema-valid and marked aborted
    path = rt.observer.trace_path
    doc = json.load(open(path))
    assert doc["otherData"]["aborted"] is True
    assert obs.validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    # the span the fault tripped inside was closed by the unwinding
    assert "expand" in names and "superstep" in names
    # --check passes on the aborted doc, schema problems still rejected
    assert render_trace.check(doc) == []
    assert render_trace.main(["--check", path]) == 0
    # JSONL flushed: the aborted superstep's spans plus a terminal record
    jsonl = path.replace(".trace.json", ".events.jsonl")
    records = [json.loads(l) for l in open(jsonl)]
    assert records[-1]["event"] == "aborted"
    assert any(
        r["event"] == "span" and r["name"] == "expand" for r in records
    )


# ---------------------------------------------------------------------------
# RunStats summary additions + render_trace CLI
# ---------------------------------------------------------------------------

def test_summary_has_bytes_and_phase_walls():
    g = _graph()
    res = SuperstepRuntime(g, MotifsApp(max_size=3), RunConfig()).run()
    s = res.stats.summary()
    assert s["total_bytes_to_host"] == res.stats.total_bytes_to_host > 0
    walls = s["phase_walls_s"]
    assert set(walls) == {
        "t_expand", "t_aggregate", "t_canon", "t_storage", "t_gather",
        "t_exchange", "t_checkpoint",
    }
    assert walls["t_expand"] > 0


def test_render_trace_cli(tmp_path, capsys):
    # --check enforces the hard ≥95% gate, which a warm sub-millisecond
    # unit-test run cannot deterministically meet (the CI --check runs
    # on the real mico_like trace) — so the pass case uses a synthetic
    # trace with perfect coverage, and the real run exercises summary
    # mode only.
    def x(name, ts, dur, parent=None):
        e = {"ph": "X", "name": name, "ts": ts, "dur": dur,
             "pid": 1, "tid": 0, "cat": "host", "args": {"step": 1}}
        if parent:
            e["args"]["parent"] = parent
        return e
    good = tmp_path / "good.trace.json"
    good.write_text(json.dumps({"traceEvents": [
        x("superstep", 0, 100),
        x("materialize", 0, 10, "superstep"),
        x("aggregate", 10, 20, "superstep"),
        x("expand", 30, 60, "superstep"),
        x("seal", 90, 10, "superstep"),
    ]}))
    assert render_trace.main(["--check", str(good)]) == 0
    assert "OK" in capsys.readouterr().out
    # summary mode on a real traced run
    g = _graph()
    cfg = RunConfig(trace=True, trace_dir=str(tmp_path))
    res = SuperstepRuntime(g, MotifsApp(max_size=3), cfg).run()
    assert render_trace.main([res.trace_path]) == 0
    out = capsys.readouterr().out
    assert "superstep" in out and "coverage=" in out
    # a truncated trace fails --check
    bad = tmp_path / "bad.trace.json"
    bad.write_text(json.dumps({"traceEvents": []}))
    assert render_trace.main(["--check", str(bad)]) == 1


# ---------------------------------------------------------------------------
# spans on the profiler's clock: TraceAnnotation per span
# ---------------------------------------------------------------------------

def test_span_opens_trace_annotation_only_while_traced(monkeypatch):
    opened = []

    class FakeAnnotation:
        def __init__(self, name, **meta):
            opened.append((name, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracer_lib, "TraceAnnotation", FakeAnnotation)
    assert tracer_lib.current() is None
    with obs.span("expand", step=2):
        pass
    assert opened == []                 # disabled: no profiler machinery
    assert obs.span("a") is obs.span("b")   # still the shared no-op
    tr = tracer_lib.Tracer()
    tracer_lib.install(tr)
    try:
        with obs.span("superstep", step=2):
            with obs.span("expand.fetch", rows=5):
                pass
        with obs.span("cost_model"):
            pass
    finally:
        tracer_lib.install(None)
    # children inherit the enclosing step; every span carries the run id
    assert opened == [
        ("superstep", {"run": tr.run_id, "step": 2}),
        ("expand.fetch", {"run": tr.run_id, "step": 2}),
        ("cost_model", {"run": tr.run_id}),
    ]
    assert tracer_lib.Tracer().run_id != tr.run_id


def _profiled(tmp_path, work):
    """Host events ``(name, stats)`` of a ``jax.profiler`` capture of
    ``work()`` on the CPU."""
    import os

    import jax

    d = str(tmp_path / "prof")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    paths = [os.path.join(dp, f) for dp, _, fs in os.walk(d)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(paths) == 1
    prof = jax.profiler.ProfileData.from_file(paths[0])
    return [(ev.name, dict(ev.stats)) for plane in prof.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


#: every program span of the expansion and aggregation taxonomy
SUB_SPANS = (
    "expand.plan", "expand.dispatch", "expand.sync", "expand.fetch",
    "expand.fold", "aggregate.bin", "aggregate.drain", "aggregate.domains",
    "canonicalize.submit",
)


def test_profiler_capture_holds_program_spans(tmp_path):
    g = _graph()
    configs = [
        (MotifsApp(max_size=3), RunConfig(
            trace=True, canonical_placement="host_async")),
        (MotifsApp(max_size=3), RunConfig(trace=True, async_chunks=False)),
        (FSMApp(support=3, max_size=3), RunConfig(trace=True)),
    ]
    runs = []

    def work():
        for app, cfg in configs:
            rt = SuperstepRuntime(g, app, cfg)
            rt.run()
            runs.append(rt.observer.tracer.run_id)
        # a span opened on another thread lands on that thread's line
        tr = tracer_lib.Tracer()
        tracer_lib.install(tr)
        try:
            t = threading.Thread(target=lambda: _enter(obs.span("level2")))
            t.start()
            t.join(timeout=60)
            assert not t.is_alive()
        finally:
            tracer_lib.install(None)

    events = _profiled(tmp_path, work)
    names = {n for n, _ in events}
    want = {"superstep", "materialize", "aggregate", "alpha", "expand",
            "seal", "canonicalize", "level2", *SUB_SPANS}
    assert want <= names, want - names
    fetch = [s for n, s in events if n == "expand.fetch"]
    assert {s["run"] for s in fetch} <= set(runs)
    assert all("step" in s for s in fetch)


def _enter(ctx):
    with ctx:
        pass


@pytest.mark.parametrize("async_chunks", [True, False])
@pytest.mark.parametrize("app_name", ["motifs", "fsm"])
def test_sub_spans_nest_under_their_phases(app_name, async_chunks):
    g = _graph()
    rt = SuperstepRuntime(
        g, APPS[app_name](), RunConfig(trace=True, async_chunks=async_chunks)
    )
    rt.run()
    spans = rt.observer.tracer.spans
    seen = set()
    for sp in spans:
        head = sp.name.split(".")[0]
        if sp.name in SUB_SPANS:
            seen.add(sp.name)
            parent = "aggregate" if head == "canonicalize" else head
            assert sp.parent == parent, (sp.name, sp.parent)
    assert {"expand.plan", "expand.dispatch", "expand.sync",
            "expand.fetch", "expand.fold", "aggregate.drain"} <= seen
    if app_name == "fsm":
        assert "aggregate.domains" in seen


# ---------------------------------------------------------------------------
# always-on counters: lanes, device reads, rows to host, process totals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("async_chunks", [True, False])
@pytest.mark.parametrize("app_name", ["motifs", "fsm", "cliques"])
def test_lanes_count_every_dispatch(app_name, async_chunks):
    g = _graph()
    app = APPS[app_name]()
    # capacity 1 forces retries: the chip computes those lanes again
    rt = SuperstepRuntime(g, app, RunConfig(
        async_chunks=async_chunks, initial_capacity=1, chunk_size=64))
    backend = rt.backend
    fn, lanes, calls = backend._expand_fn, [], []

    def counted(g_, members, n_valid, out_cap, n_lanes=None):
        # the packed lane bucket the program computes, else its padded
        # (c, k, D) table (k = 2·size endpoint slots in edge mode)
        c, k = members.shape
        k = k if app.mode == "vertex" else 2 * k
        lanes.append(n_lanes if n_lanes is not None
                     else c * k * g_.max_degree)
        kw = {} if n_lanes is None else {"n_lanes": n_lanes}
        return fn(g_, members, n_valid, out_cap=out_cap, **kw)

    backend._expand_fn = counted
    res = rt.run()
    steps = res.stats.steps
    assert sum(s.n_lanes for s in steps) == sum(lanes) > 0
    assert len(lanes) > sum(s.n_chunks for s in steps)     # retries seen
    for s in steps:
        assert s.n_generated <= s.n_lanes


@pytest.mark.parametrize("async_chunks", [True, False])
@pytest.mark.parametrize("app_name", ["motifs", "fsm", "cliques"])
def test_device_reads_and_rows_to_host(app_name, async_chunks):
    g = _graph()
    rt = SuperstepRuntime(g, APPS[app_name](),
                          RunConfig(async_chunks=async_chunks))
    store, received = rt.store, []
    append = store.append

    def recorded(rows):
        received.append(np.asarray(rows).nbytes)
        return append(rows)

    store.append = recorded
    res = rt.run()
    steps = res.stats.steps
    # the first append is the loop's initial frontier, not an expansion
    assert sum(s.rows_to_host for s in steps) == sum(received[1:]) > 0
    for s in steps:
        assert s.n_device_reads >= s.n_host_syncs
    assert sum(s.n_device_reads for s in steps) > sum(
        s.n_host_syncs for s in steps)


def test_totals_grow_by_each_completed_run():
    g = _graph()
    for cfg in (RunConfig(), RunConfig(trace=True)):
        before = obs.totals()
        res = SuperstepRuntime(g, MotifsApp(max_size=3), cfg).run()
        after = obs.totals()
        steps = res.stats.steps
        grew = {k: after[k] - before.get(k, 0) for k in after}
        assert grew["runs"] == 1 and grew["supersteps"] == len(steps)
        for k in ("n_generated", "n_lanes", "n_host_syncs",
                  "n_device_reads", "rows_to_host", "bytes_to_host",
                  "n_chunks", "n_children"):
            assert grew[k] == sum(getattr(s, k) for s in steps), k
        assert "step" not in after and "size" not in after
        assert "t_expand" not in after            # floats are not counted
    # a run that dies folds nothing
    before = obs.totals()
    from repro.core.runtime import FaultPlan, FaultSpec
    with pytest.raises(Exception, match="injected"):
        SuperstepRuntime(g, MotifsApp(max_size=3), RunConfig(
            faults=FaultPlan([FaultSpec("expand", 2, "crash")]))).run()
    assert obs.totals() == before


def test_registry_keeps_counters_and_gauges_only():
    reg = metrics_lib.MetricsRegistry()
    assert not hasattr(reg, "observe") and not hasattr(reg, "dists")
    assert set(reg.snapshot()) == {"counters", "gauges", "gauge_max"}
