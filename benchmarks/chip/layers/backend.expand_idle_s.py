"""Serial backend: seconds per traced job in which the chip sat idle while
the host was inside expansion: the idle gaps of the trace labelled
``expand`` or one of its sub-spans (``expand.plan``, ``expand.dispatch``,
``expand.sync``, ``expand.fetch``, ``expand.fold``), over the traced jobs.
The reduction keeps its top 10 idle labels: a label left out holds less
idle than the tenth, so the sum falls short by less than that for each
expansion label left out."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["n_jobs"]:
        return None
    idle = sum(s for name, s in t["idle_gaps"]
               if name == "expand" or str(name).startswith("expand."))
    return idle / t["n_jobs"]
