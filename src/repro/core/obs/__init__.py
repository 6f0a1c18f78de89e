"""Structured observability for the superstep runtime (DESIGN.md §12).

The façade every runtime layer imports as ``from repro.core import obs``:

  * ``obs.span("expand", step=k, ...)`` — nested host phase spans, each
    also a ``jax.profiler.TraceAnnotation`` (a shared nullcontext when no
    tracer is installed: zero allocation, zero device syncs on the
    disabled path);
  * ``obs.count(st, "bytes_to_host", n)`` / ``obs.set_stat(...)`` — THE
    write path for StepStats counters, bit-identical to the inline
    mutations it replaced, mirrored into the metrics registry while
    observing;
  * ``obs.fence(*trees)`` — blocking phase boundaries, ONLY under
    ``trace_sync=True``;
  * ``obs.to_host(x)`` — a counted blocking device→host read
    (``StepStats.n_device_reads``);
  * ``obs.totals()`` — cumulative counters of every run the process
    completed, for an operator to scrape;
  * :class:`RunObserver` — the per-run bundle the loop drives (install,
    per-step counters + progress log, Chrome-trace/JSONL export).

Knobs: ``RunConfig.trace`` / ``trace_dir`` / ``trace_sync`` /
``log_every``.
"""
from repro.core.obs.export import (              # noqa: F401
    PHASES,
    RunObserver,
    chrome_trace_events,
    phase_coverage,
    step_log_line,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.core.obs.metrics import (             # noqa: F401
    MetricsRegistry,
    count,
    fold_run,
    gauge,
    read_count,
    sample_device_memory,
    set_stat,
    to_host,
    totals,
)
from repro.core.obs.metrics import (             # noqa: F401
    current as current_metrics,
)
from repro.core.obs.metrics import (             # noqa: F401
    install as install_metrics,
)
from repro.core.obs.tracer import (              # noqa: F401
    Span,
    Tracer,
    fence,
    probe_time,
    span,
    sync_active,
)
from repro.core.obs.tracer import (              # noqa: F401
    current as current_tracer,
)
from repro.core.obs.tracer import (              # noqa: F401
    install as install_tracer,
)
