"""Chunk program: device seconds per job of the jitted chunk program
(``programs.make_expand_fn``'s ``fn``: expansion, canonicality check, app
filter, compaction and the child codes or level-1 partials, with the
Pallas kernels it calls), from the profiler trace."""

#: the chunk program's name in the trace (the jitted function is ``fn``)
PROGRAMS = ("jit_fn",)


def device_s(trace):
    return sum(s for name, s in trace["program_s"].items() if name in PROGRAMS)


def read(ctx):
    if ctx.trace is None or not ctx.trace["n_jobs"]:
        return None
    s = device_s(ctx.trace)
    return s / ctx.trace["n_jobs"] if s > 0 else None
