"""The check that decides ``correct``: a sound run passes; the control
(``control.py``) and each fault planted in the timed path fail it.

The runs skip the harness's look for a chip and drive the rest of a run
on a small graph: set-up, a short window, the reference, the check."""
import io
from types import SimpleNamespace

import numpy as np
import pytest

import control
import run
from repro.core.runtime import SerialBackend, programs
from repro.core import aggregation

TINY = {
    "graph": {"vertices": 60, "edges": 170, "labels": 3, "base_seed": 5},
    "run_config": {"chunk_size": 32},
}
#: the traffic mixes of the benchmark, and one more of each app that
#: exists as parameters only
TRAFFIC = {
    "motifs3": {"app": "motifs", "params": {"max_size": 3}},
    "cliques4": {"app": "cliques", "params": {"max_size": 4}},
    "motifs2": {"app": "motifs", "params": {"max_size": 2}},
    "cliques5": {"app": "cliques", "params": {"max_size": 5}},
}


def cell(traffic):
    t = TRAFFIC[traffic]
    return SimpleNamespace(
        name=f"tiny.{traffic}", chips=1, config=TINY,
        job=SimpleNamespace(name=t["app"], params=t["params"],
                            module=run.load_module("apps", t["app"])),
        end_to_end=[{"name": "setup_s", "unit": "s"},
                    {"name": "job_s", "unit": "s"}],
        per_layer=[])


def execute(traffic, seed=3, **kw):
    return run.execute(cell(traffic), seed, 0.2, False, require_tpu=False,
                       out=io.StringIO(), **kw)


def failed_numbers(res):
    return {k: v["value"] for k, v in res["check"].items() if v["value"]}


@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
def test_sound_run_is_correct(traffic):
    res = execute(traffic)
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert set(res["metrics"]) == {"setup_s", "job_s"}


@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_control_is_not_correct(traffic, seed):
    res = execute(traffic, seed=seed, mine=control.mine)
    assert not res["correct"]
    assert res["failed"] == res["attempted"]
    assert failed_numbers(res)["keys_wrong"] > 0


def _state_unchanged(monkeypatch):
    """A superstep that expands nothing: the frontier never grows."""
    monkeypatch.setattr(SerialBackend, "expand",
                        lambda self, store, blocks, size, st: None)


def _half_batch(monkeypatch):
    """Half of every superstep's chunks left out."""
    real = programs.iter_chunks

    def half(*a, **k):
        chunks = list(real(*a, **k))
        return iter(chunks[: max(1, len(chunks) // 2)])

    monkeypatch.setattr(programs, "iter_chunks", half)


def _answer_altered(monkeypatch):
    """One answer altered where it is produced: a pattern count (motifs)
    or one member of the materialised frontier (cliques)."""
    real_build = aggregation.build_step_aggregates

    def build(table, counts, *a, **k):
        counts = np.array(counts, copy=True)
        if len(counts):
            counts[0] += 1
        return real_build(table, counts, *a, **k)

    monkeypatch.setattr(aggregation, "build_step_aggregates", build)
    real_begin = SerialBackend.begin_step

    def begin(self, store, st):
        waves = real_begin(self, store, st)
        if st.size >= 2 and waves and len(waves[0]):
            waves[0] = np.array(waves[0], copy=True)
            waves[0][0, -1] = (waves[0][0, -1] + 1) % self.g.n
            self._waves = waves
        return waves

    monkeypatch.setattr(SerialBackend, "begin_step", begin)


FAULTS = {
    "state_unchanged": _state_unchanged,
    "half_batch": _half_batch,
    "answer_altered": _answer_altered,
}


@pytest.mark.parametrize("traffic", ["cliques4", "motifs3"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(monkeypatch, traffic, fault):
    programs._CHUNK_PROGRAM_CACHE.clear()
    FAULTS[fault](monkeypatch)
    res = execute(traffic)
    assert not res["correct"], (fault, res["check"])
    assert failed_numbers(res)
