"""The trace reduction on a small trace: busy union, idle share, program
grouping and gap attribution."""
from types import SimpleNamespace

import pytest

import tracereduce as tr

MS = 1_000_000  # ns


def small_trace():
    # two jobs: [0, 10) ms and [12, 20) ms; host idle between them
    ops = [
        ("fusion.1", "jit_fn", 1 * MS, 2 * MS),      # [1, 3)
        ("fusion.2", "jit_fn", 2 * MS, 2 * MS),      # [2, 4) overlaps
        ("sort.3", "jit__bin_weighted", 6 * MS, 1 * MS),   # [6, 7)
        ("fusion.1", "jit_fn", 13 * MS, 3 * MS),     # [13, 16)
        ("copy.9", "jit_other", 30 * MS, 1 * MS),    # outside the jobs
    ]
    modules = [
        ("jit_fn(1)", 1 * MS, 3 * MS),
        ("jit__bin_weighted(2)", 6 * MS, 1 * MS),
        ("jit_fn(1)", 13 * MS, 3 * MS),
        ("jit_other(3)", 30 * MS, 1 * MS),
    ]
    host = [("bench.job", 0, 10 * MS), ("bench.job", 12 * MS, 8 * MS),
            ("PjitFunction(fn)", 4 * MS, 2 * MS)]
    spans = [
        ("superstep", 0, 10 * MS, 0),
        ("expand", 0, 5 * MS, 1),
        ("aggregate", 5 * MS, 5 * MS, 1),
        ("superstep", 12 * MS, 8 * MS, 0),
        ("expand", 12 * MS, 5 * MS, 1),
    ]
    return {"ops": ops, "modules": modules, "host": host,
            "jobs": [(0, 10 * MS), (12 * MS, 20 * MS)], "spans": spans}


def test_busy_is_the_union_inside_the_window():
    red = tr.reduce(small_trace())
    assert red["n_jobs"] == 2
    assert red["window_s"] == pytest.approx(0.020)
    # [1, 4) + [6, 7) + [13, 16) = 7 ms; the op at 30 ms is outside
    assert red["busy_s"] == pytest.approx(0.007)
    idle_share = 1 - red["busy_s"] / red["window_s"]
    assert idle_share == pytest.approx(0.65)


def test_program_grouping_strips_run_ids():
    red = tr.reduce(small_trace())
    assert red["program_s"] == pytest.approx(
        {"jit_fn": 0.006, "jit__bin_weighted": 0.001})
    ops = dict((k, v) for k, v in red["device_ops"])
    assert ops["jit_fn:fusion.1"] == pytest.approx(0.005)
    assert ops["jit__bin_weighted:sort.3"] == pytest.approx(0.001)


def test_program_grouping_from_ops_alone():
    data = small_trace()
    data["modules"] = []
    red = tr.reduce(data)
    # per program the union of its ops: [1, 4) and [13, 16) for jit_fn
    assert red["program_s"]["jit_fn"] == pytest.approx(0.006)


def test_gaps_go_to_the_innermost_span():
    red = tr.reduce(small_trace())
    idle = dict((k, v) for k, v in red["idle_gaps"])
    # gaps: [0,1), [4,6), [7,13), [16,20). Cut at the spans' and jobs'
    # edges: [0,1) [4,5) [12,13) [16,17) in expand; [5,6) [7,10) in
    # aggregate; [10,12) between the jobs; [17,20) in the superstep
    assert idle["expand"] == pytest.approx(0.004)
    assert idle["aggregate"] == pytest.approx(0.004)
    assert idle["superstep"] == pytest.approx(0.003)
    assert idle["between jobs"] == pytest.approx(0.002)
    assert red["n_gaps"] == 4
    # the idle gaps and the busy time cover the window exactly
    assert sum(idle.values()) + red["busy_s"] == pytest.approx(0.020)


def test_gap_falls_back_to_host_events():
    data = small_trace()
    data["spans"] = []
    idle = dict((k, v) for k, v in tr.reduce(data)["idle_gaps"])
    assert idle["PjitFunction(fn)"] == pytest.approx(0.002)   # [4, 6)
    assert idle["between jobs"] == pytest.approx(0.011)
    assert sum(idle.values()) == pytest.approx(0.013)


def test_merge_and_gaps():
    assert tr.merge([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert tr.gaps([(1, 4), (5, 6)], 0, 8) == [(0, 1), (4, 5), (6, 8)]
    assert tr.gaps([], 0, 2) == [(0, 2)]


def _ev(name, start, dur, **stats):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                           stats=stats)


def test_extract_reads_planes_and_aligns_spans():
    device = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules",
                        events=[_ev("jit_fn(4)", 100 * MS, 5 * MS)]),
        SimpleNamespace(name="XLA Ops", events=[
            _ev("fusion.1", 101 * MS, 2 * MS, hlo_module="jit_fn")]),
    ])
    host = SimpleNamespace(name="/host:CPU", lines=[
        SimpleNamespace(name="main", events=[
            _ev("bench.job", 100 * MS, 10 * MS)])])
    profile = SimpleNamespace(planes=[device, host])
    # the job's host clock read 50.0 s at the annotation's start; its one
    # span opened 1 ms later
    data = tr.extract(profile, job_marks=[50.0],
                      job_spans=[[("expand", 50.001, 0.004, 1)]])
    assert data["jobs"] == [(100 * MS, 110 * MS)]
    assert data["ops"] == [("fusion.1", "jit_fn", 101 * MS, 2 * MS)]
    (name, start, dur, depth), = data["spans"]
    assert name == "expand" and depth == 1
    assert start == pytest.approx(101 * MS, abs=1e3)
    assert dur == pytest.approx(4 * MS, abs=1e3)


def _recorded():
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "recorded_trace.json")
    with open(path, encoding="utf-8") as f:
        rec = json.load(f)
    t = rec["trace"]
    return {
        "ops": [tuple(o) for o in t["ops"]],
        "modules": [tuple(m) for m in t["modules"]],
        "host": [tuple(h) for h in t["host"]],
        "jobs": [tuple(j) for j in t["jobs"]],
        "spans": [tuple(s) for s in t["spans"]],
    }


def test_recorded_trace():
    """The first 400 device ops of one warm ``citeseer.motifs3`` job,
    recorded on a TPU v5 lite and read by :func:`tracereduce.extract`
    (ops there carry no module name; the ``XLA Modules`` line gave
    them theirs)."""
    data = _recorded()
    red = tr.reduce(data)
    (lo, hi), = data["jobs"]
    # busy by a plain sweep over the ops, clipped to the job
    ends = []
    busy = 0.0
    for _, _, s, d in sorted(data["ops"], key=lambda o: o[2]):
        s, e = max(s, lo), min(s + d, hi)
        if e <= s:
            continue
        if ends and s < ends[-1]:
            if e > ends[-1]:
                busy += e - ends[-1]
                ends[-1] = e
        else:
            busy += e - s
            ends.append(e)
    assert red["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0 < red["busy_s"] < red["window_s"]
    idle = dict((k, v) for k, v in red["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    # the chunk program is most of the device time, and the idle time
    # falls in the runtime's own phases
    assert max(red["program_s"], key=red["program_s"].get) == "jit_fn"
    assert max(idle, key=idle.get) in {"expand", "aggregate", "superstep"}
    # naming the ops again from the modules line changes nothing
    unnamed = [(n, "", s, d) for n, _, s, d in data["ops"]]
    assert tr._ops_in_modules(unnamed, data["modules"]) == sorted(
        data["ops"], key=lambda o: o[2])
    assert sum(1 for o in data["ops"] if o[1].startswith("jit_fn")) > 300
