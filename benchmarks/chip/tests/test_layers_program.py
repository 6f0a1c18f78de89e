"""The per-layer readers of the program's own counters and sub-spans
(``chunk.lane_fill``, ``backend.device_reads``, ``backend.rows_to_host``,
``backend.expand_idle_s``) on a traced run of a small graph, with the
harness's look for a chip skipped."""
import io
from types import SimpleNamespace

import pytest

import run
from repro.core import obs
from repro.core.obs import metrics as metrics_lib

NEW = ("chunk.lane_fill", "backend.device_reads", "backend.rows_to_host",
       "backend.expand_idle_s")
OLD = ("loop.expand_s", "backend.host_syncs", "device.idle_share")

TINY = {
    "graph": {"vertices": 60, "edges": 170, "labels": 3, "base_seed": 5},
    "run_config": {"chunk_size": 32},
}
TRAFFIC = {
    "motifs3": {"app": "motifs", "params": {"max_size": 3}},
    "cliques4": {"app": "cliques", "params": {"max_size": 4}},
}


def cell(traffic, metrics):
    t = TRAFFIC[traffic]
    return SimpleNamespace(
        name=f"tiny.{traffic}", chips=1, config=TINY,
        job=SimpleNamespace(name=t["app"], params=t["params"],
                            module=run.load_module("apps", t["app"])),
        end_to_end=[{"name": "job_s", "unit": "s"}],
        per_layer=[{"name": m, "unit": "-"} for m in metrics])


def traced(traffic, metrics=NEW + OLD):
    return run.execute(cell(traffic, metrics), 2**31 + 11, 0.2, True,
                       require_tpu=False, out=io.StringIO())


@pytest.fixture
def fresh_totals(monkeypatch):
    """Process totals that hold this test's runs alone."""
    monkeypatch.setattr(metrics_lib, "_TOTALS", {})


@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
def test_readers_on_a_traced_run(fresh_totals, traffic):
    res = traced(traffic)
    assert res["correct"], res["check"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(m) >= set(NEW) | {"loop.expand_s", "backend.host_syncs"}
    assert 0 < m["chunk.lane_fill"] <= 100
    assert m["backend.device_reads"] >= m["backend.host_syncs"] > 0
    assert m["backend.rows_to_host"] > 0
    assert 0 <= m["backend.expand_idle_s"]
    # the totals hold the warm-up job and every job of the window
    t = obs.totals()
    assert t["runs"] == res["attempted"] + 1
    assert m["backend.rows_to_host"] == t["rows_to_host"] / t["runs"]


def test_counter_readers_give_nothing_without_totals(fresh_totals,
                                                     monkeypatch):
    """A program without ``obs.totals`` (the parent of the change that
    added it) reads as no value, and nothing raises."""
    monkeypatch.delattr(obs, "totals")
    res = traced("cliques4", NEW)
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"backend.expand_idle_s"}
