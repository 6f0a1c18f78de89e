"""Runtime loop: host wall of the expansion phase per job (``t_expand``,
the loop's lap around the chunk programs' dispatch and drains)."""


def read(ctx):
    if not ctx.jobs:
        return None
    return sum(j["phase_walls"]["t_expand"] for j in ctx.jobs) / len(ctx.jobs)
