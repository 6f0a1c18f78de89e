"""Reduction of a profiler trace to device busy time, program time and
idle gaps.

The trace is first read into plain lists (:func:`extract`), so that the
reduction can be tested on a small recorded trace:

* ``ops``: ``(op, module, start_ns, dur_ns)`` for every operation that ran
  on the device (the ``XLA Ops`` line of each device plane);
* ``modules``: ``(module, start_ns, dur_ns)`` for every program run (the
  ``XLA Modules`` line);
* ``host``: ``(name, start_ns, dur_ns)`` of the annotations and runtime
  events on the host thread that ran the jobs;
* ``jobs``: ``(start_ns, end_ns)`` of each traced mining job;
* ``spans``: ``(name, start_ns, dur_ns, depth)`` of the program's own
  phase spans (superstep > materialize, aggregate, expand, seal, ...),
  moved onto the profiler's clock.

Busy time is the union of the operations' intervals inside the jobs'
window; every stretch of the window that no operation covers is an idle
gap, cut where a job or a phase span begins or ends; each piece is named
by the innermost phase span open at its middle (else the innermost host
event, else ``"between jobs"``).
"""
from __future__ import annotations

import re
from collections import defaultdict

import numpy as np

_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """``jit_fn(12)`` -> ``jit_fn``: a program's name without its run id."""
    return _SUFFIX.sub("", str(name)).strip()


def op_name(text: str) -> str:
    """``%fusion.1 = u32[...] fusion(...)`` -> ``fusion.1``: an operation's
    name without its HLO text."""
    return str(text).split(" = ", 1)[0].strip().lstrip("%")


def merge(intervals):
    """Sorted union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def gaps(merged, lo, hi):
    """The stretches of ``[lo, hi)`` that ``merged`` leaves uncovered."""
    out, at = [], lo
    for s, e in clip(merged, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def label_pieces(starts, ends, events):
    """Name each piece ``[starts[i], ends[i])`` by the shortest event
    ``(name, start, dur, ...)`` open at its middle, or None."""
    mid = (np.asarray(starts, np.float64) + np.asarray(ends, np.float64)) / 2
    out = np.full(len(mid), None, dtype=object)
    # longest first, so that the innermost open event is written last
    for ev in sorted(events, key=lambda ev: -ev[2]):
        out[(ev[1] <= mid) & (mid < ev[1] + ev[2])] = ev[0]
    return out.tolist()


def reduce(data, top: int = 10) -> dict:
    """Window, busy and per-program device seconds of the traced jobs,
    with the ``top`` device operations and idle-gap labels by time."""
    jobs = sorted(data["jobs"])
    if not jobs:
        return None
    lo, hi = jobs[0][0], jobs[-1][1]
    ops = [o for o in data["ops"] if o[2] < hi and o[2] + o[3] > lo]
    busy = clip(merge((o[2], o[2] + o[3]) for o in ops), lo, hi)
    busy_ns = sum(e - s for s, e in busy)

    program_ns = defaultdict(float)
    if data.get("modules"):
        for name, s, d in data["modules"]:
            if s < hi and s + d > lo:
                program_ns[module_name(name)] += min(s + d, hi) - max(s, lo)
    else:
        by_module = defaultdict(list)
        for _, mod, s, d in ops:
            by_module[module_name(mod)].append((s, s + d))
        for mod, iv in by_module.items():
            program_ns[mod] = sum(e - s for s, e in clip(merge(iv), lo, hi))

    op_ns = defaultdict(float)
    for name, mod, s, d in ops:
        op_ns[f"{module_name(mod)}:{op_name(name)}"] += (
            min(s + d, hi) - max(s, lo))

    # each gap is cut where a job or a phase span opens or closes; a piece
    # is named by the innermost phase span open at its middle, else by
    # the innermost host event of the jobs' thread, else "between jobs"
    spans = data.get("spans", ())
    cuts = sorted({t for j in jobs for t in j}
                  | {t for _, s, d, _ in spans for t in (s, s + d)})
    cuts = np.asarray(cuts, np.float64)
    starts, ends = [], []
    all_gaps = gaps(busy, lo, hi)
    for s, e in all_gaps:
        inner = cuts[(cuts > s) & (cuts < e)].tolist()
        edges = [s, *inner, e]
        starts += edges[:-1]
        ends += edges[1:]
    names = label_pieces(starts, ends, spans)
    rest = [i for i, n in enumerate(names) if n is None]
    if rest:
        host = [h for h in data.get("host", ()) if h[0] != "bench.job"]
        found = label_pieces([starts[i] for i in rest],
                             [ends[i] for i in rest], host)
        for i, n in zip(rest, found):
            names[i] = n or "between jobs"
    idle = defaultdict(float)
    for n, s, e in zip(names, starts, ends):
        idle[n] += e - s
    n_gaps = len(all_gaps)

    def top_list(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "n_jobs": len(jobs),
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "n_gaps": n_gaps,
        "program_s": {k: v / 1e9 for k, v in program_ns.items()},
        "device_ops": top_list(op_ns),
        "idle_gaps": top_list(idle),
    }


# ---------------------------------------------------------------------------
# reading a profiler trace
# ---------------------------------------------------------------------------

def extract(profile, job_marks=(), job_spans=()) -> dict:
    """Plain lists from a ``jax.profiler.ProfileData``.

    ``job_marks`` holds, per traced job, the host clock (seconds of
    ``time.perf_counter``) read just inside its ``bench.job`` annotation;
    ``job_spans`` the same job's phase spans as ``(name, perf_start_s,
    dur_s, depth)``. The k-th ``bench.job`` event of the trace gives the
    offset that moves the k-th job's spans onto the profiler's clock."""
    ops, modules, host = [], [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        modules.append((ev.name, ev.start_ns, ev.duration_ns))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        stats = dict(ev.stats)
                        ops.append((ev.name, str(stats.get("hlo_module", "")),
                                    ev.start_ns, ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.duration_ns)
                       for ev in line.events]
                # the jobs' own thread: the one that holds the annotations
                if any(e[0] == "bench.job" for e in evs):
                    host += [e for e in evs if e[2] > 0]
    if not any(o[1] for o in ops) and modules:
        ops = _ops_in_modules(ops, modules)
    job_events = sorted(h for h in host if h[0] == "bench.job")
    jobs = [(s, s + d) for _, s, d in
            sorted((h for h in job_events), key=lambda h: h[1])]
    spans = []
    for k, (_, s, _) in enumerate(sorted(job_events, key=lambda h: h[1])):
        if k >= len(job_marks):
            break
        base = s - job_marks[k] * 1e9
        for name, ps, pd, depth in job_spans[k]:
            spans.append((name, base + ps * 1e9, pd * 1e9, depth))
    return {"ops": ops, "modules": modules, "host": host,
            "jobs": jobs, "spans": spans}


def _ops_in_modules(ops, modules):
    """Name each op's program by the module run that encloses it."""
    mods = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for name, _, s, d in sorted(ops, key=lambda o: o[2]):
        while j + 1 < len(mods) and mods[j + 1][1] <= s:
            j += 1
        mod = ""
        if mods and mods[j][1] <= s < mods[j][1] + mods[j][2]:
            mod = mods[j][0]
        out.append((name, mod, s, d))
    return out
