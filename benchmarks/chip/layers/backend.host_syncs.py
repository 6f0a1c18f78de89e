"""Serial backend: times per job the host blocked on a device value to
decide control flow (``StepStats.n_host_syncs``, summed over supersteps)."""


def read(ctx):
    if not ctx.jobs:
        return None
    return sum(j["host_syncs"] for j in ctx.jobs) / len(ctx.jobs)
