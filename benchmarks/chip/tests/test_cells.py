"""Every cell of BENCHMARK.json resolves to files found by name: its
configuration, its traffic mix and the app module that mix names, and a
reader for each of its per-layer metrics."""
import json
import os

import pytest

import run
from conftest import ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

APP_API = ("make", "answer", "reference_answer", "compare", "control",
           "LIMITS")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(name):
    cell = run.load_cell(name)
    assert cell.chips in (1, 4)
    for attr in APP_API:
        assert hasattr(cell.job.module, attr), (cell.job.name, attr)
    assert set(cell.job.module.LIMITS) >= {"keys_wrong", "count_gap"}
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "job_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(run.load_reader(m["name"]))


def test_unknown_app_is_refused():
    with pytest.raises(run.Refused):
        run.load_module("apps", "no-such-app")
