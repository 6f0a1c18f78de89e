"""Serial backend: bytes per job drained to the host by pattern
aggregation (``StepStats.bytes_to_host``, summed over supersteps)."""


def read(ctx):
    if not ctx.jobs:
        return None
    return sum(j["bytes_to_host"] for j in ctx.jobs) / len(ctx.jobs)
