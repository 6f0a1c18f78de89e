"""Aggregation: device seconds per job of the level-1 bin and merge
programs and the level-2 refine program (``core/aggregation.py``,
``kernels/aggregate.py``, ``radix_bin.py``, ``canonical_refine.py``),
from the profiler trace."""

PROGRAMS = (
    "jit__bin_all_valid", "jit__bin_weighted", "jit__finish_flags",
    "jit__level2_program", "jit_refine_batch", "jit__quick_patterns",
)


def read(ctx):
    if ctx.trace is None or not ctx.trace["n_jobs"]:
        return None
    s = sum(v for k, v in ctx.trace["program_s"].items() if k in PROGRAMS)
    return s / ctx.trace["n_jobs"] if s > 0 else None
