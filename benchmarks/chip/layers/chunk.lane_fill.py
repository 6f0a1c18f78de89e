"""Chunk program: lane fill, the share of the candidate lanes the chunk
programs computed that held a valid candidate: 100 * sum(n_generated) /
sum(n_lanes) (``StepStats.n_lanes``: bucket rows x k x D of every
dispatch, capacity retries included), from the program's process totals
(``obs.totals()``). The totals hold the warm-up job and the window's
jobs, which mine one graph with one app and one decision table, so the
ratio is the per-job one. A program without the counter gives nothing."""


def _totals():
    from repro.core import obs

    totals = getattr(obs, "totals", None)
    return totals() if totals is not None else None


def read(ctx):
    t = _totals()
    if not t or not t.get("n_lanes"):
        return None
    return 100.0 * t["n_generated"] / t["n_lanes"]
