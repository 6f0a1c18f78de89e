"""Serial backend: blocking device-to-host reads per job
(``StepStats.n_device_reads``: control syncs, drains of child rows,
carried codes and aggregation tables), from the program's process totals
(``obs.totals()``) over its runs. The totals hold the warm-up job and the
window's jobs, all alike, so the mean is the per-job one. A program
without the counter gives nothing."""


def _totals():
    from repro.core import obs

    totals = getattr(obs, "totals", None)
    return totals() if totals is not None else None


def read(ctx):
    t = _totals()
    if not t or not t.get("runs") or "n_device_reads" not in t:
        return None
    return t["n_device_reads"] / t["runs"]
