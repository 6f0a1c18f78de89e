"""Runtime loop: host wall of pattern aggregation per job, level 1 and
level 2. ``t_aggregate`` already holds a synchronous level 2; under the
``host_async`` placement only the join's residual wait (``t_canon``) lies
outside it, so it is added there alone."""


def read(ctx):
    if not ctx.jobs:
        return None
    overlapped = ctx.decisions.get("canonical_placement") == "host_async"
    total = 0.0
    for j in ctx.jobs:
        w = j["phase_walls"]
        total += w["t_aggregate"] + (w["t_canon"] if overlapped else 0.0)
    return total / len(ctx.jobs)
