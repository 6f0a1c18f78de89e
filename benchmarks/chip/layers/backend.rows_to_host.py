"""Serial backend: bytes per job of child rows, carried codes and
local-vertex tables drained into the host store during expansion
(``StepStats.rows_to_host``; the next frontier's trip through the host),
from the program's process totals (``obs.totals()``) over its runs. The
totals hold the warm-up job and the window's jobs, all alike, so the
mean is the per-job one. A program without the counter gives nothing."""


def _totals():
    from repro.core import obs

    totals = getattr(obs, "totals", None)
    return totals() if totals is not None else None


def read(ctx):
    t = _totals()
    if not t or not t.get("runs") or "rows_to_host" not in t:
        return None
    return t["rows_to_host"] / t["runs"]
