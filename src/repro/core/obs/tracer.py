"""Low-overhead span tracer of the observability layer (DESIGN.md §12).

One :class:`Tracer` collects host-side *spans* — named, nested, attributed
wall-time intervals (``superstep`` > ``expand`` > ...) — from every layer
of the runtime through the module-level helpers in ``repro.core.obs``.
Design constraints, in order:

  * **zero new device syncs when disabled** (the default): the module-level
    ``span()`` helper returns a shared ``nullcontext`` when no tracer is
    installed, and ``fence()`` is a no-op unless the installed tracer was
    built with ``sync=True``. The disabled path performs one global read
    and no allocation.
  * **honest phase boundaries are opt-in**: JAX dispatch is asynchronous,
    so a host-side ``perf_counter`` lap at a phase boundary measures
    *dispatch*, not device completion. ``Tracer(sync=True)``
    (``RunConfig.trace_sync``) makes ``fence(*trees)`` block on the passed
    arrays at phase boundaries — the documented contract: blocking
    ``block_until_ready`` boundaries exist ONLY under ``trace_sync=True``.
  * **thread safety**: span stacks are thread-local (nesting is
    per-thread, matching Chrome trace ``tid`` semantics) and the event
    list is lock-guarded, so a future background-canonicalisation thread
    can trace into the same run.

Timestamps are microseconds since the tracer's epoch (``perf_counter``
based — monotonic, sub-µs resolution), the unit Chrome trace events use
natively.

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name, so
under a ``jax.profiler`` capture the program's spans sit on the device
trace's clock, on whichever thread opened them (TensorBoard, Perfetto).
Each annotation carries the tracer's ``run`` id and the innermost
enclosing ``step``: all spans of one run share the id.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Span:
    """One closed span: a Chrome-trace complete ("X") event's worth."""

    name: str
    ts: float                 # µs since the tracer epoch
    dur: float                # µs
    tid: int                  # small per-tracer thread index
    depth: int                # nesting depth on its thread (0 = root)
    parent: Optional[str]     # enclosing span's name (None at depth 0)
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class CounterSample:
    """One point of a named counter track (Chrome "C" event)."""

    name: str
    ts: float                 # µs since the tracer epoch
    values: Dict[str, float]


#: process-wide run ids: one per tracer, so per traced run
_RUN_IDS = itertools.count(1)


class Tracer:
    """Collects spans + counter samples for one (or more) mining runs."""

    def __init__(self, sync: bool = False,
                 on_close: Optional[Callable[[Span], None]] = None) -> None:
        self.sync = bool(sync)
        self.on_close = on_close
        self.run_id = next(_RUN_IDS)
        self.epoch = time.perf_counter()
        self.spans: List[Span] = []
        self.counters: List[CounterSample] = []
        #: fences that actually blocked — the overhead-guard observable
        self.n_fences = 0
        self._lock = threading.Lock()
        self._local = threading.local()
        self._tids: Dict[int, int] = {}

    # -- internals -----------------------------------------------------------
    def _now(self) -> float:
        return (time.perf_counter() - self.epoch) * 1e6

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    # -- recording -----------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        stack = self._stack()               # (name, step) per open span
        parent, step = stack[-1] if stack else (None, None)
        step = attrs.get("step", step)
        stack.append((name, step))
        meta = {"run": self.run_id}
        if step is not None:
            meta["step"] = int(step)
        t0 = self._now()
        try:
            with TraceAnnotation(name, **meta):
                yield
        finally:
            t1 = self._now()
            stack.pop()
            sp = Span(
                name=name, ts=t0, dur=t1 - t0,
                tid=self._tid(), depth=len(stack), parent=parent,
                args=attrs,
            )
            with self._lock:
                self.spans.append(sp)
            if self.on_close is not None:
                self.on_close(sp)

    def counter(self, name: str, **values) -> None:
        sample = CounterSample(
            name=name, ts=self._now(),
            values={k: float(v) for k, v in values.items()},
        )
        with self._lock:
            self.counters.append(sample)

    def fence(self, *trees) -> None:
        """Block until the passed pytrees are device-complete — ONLY when
        this tracer was built with ``sync=True`` (``trace_sync``). The
        accurate-phase-boundary knob; never implied by plain tracing."""
        if not self.sync:
            return
        import jax

        blocked = False
        for tree in trees:
            if tree is None:
                continue
            jax.block_until_ready(tree)
            blocked = True
        if blocked:
            self.n_fences += 1


# -- the installed tracer (module-level, what the runtime layers talk to) ----

_TRACER: Optional[Tracer] = None
#: shared reusable no-op context — the whole disabled-path cost of span()
_NULL = contextlib.nullcontext()


def install(tracer: Optional[Tracer]) -> None:
    """Make ``tracer`` the process's current tracer (None uninstalls).
    Last-install-wins: concurrent *traced* runs in one process would
    interleave into whichever tracer is current (untraced runs are
    unaffected — they never install)."""
    global _TRACER
    _TRACER = tracer


def current() -> Optional[Tracer]:
    return _TRACER


def span(name: str, **attrs):
    """A tracer span when tracing is on; a shared nullcontext otherwise."""
    t = _TRACER
    if t is None:
        return _NULL
    return t.span(name, **attrs)


def fence(*trees) -> None:
    """Phase-boundary device fence: blocks only under an installed
    ``sync=True`` tracer (the ``trace_sync`` contract); no-op — and no
    device touch — in every other configuration."""
    t = _TRACER
    if t is not None and t.sync:
        t.fence(*trees)


def sync_active() -> bool:
    """True iff an installed tracer asked for blocking phase boundaries."""
    t = _TRACER
    return t is not None and t.sync


def probe_time(fn, *args) -> float:
    """Run a jitted probe twice — once to warm the compile cache, once
    timed to completion — and return the timed seconds. Used by the
    ``trace_sync`` gather/halo probes (``StepStats.t_gather``/
    ``t_exchange``): those stages run *inside* the fused program, so
    separating them costs a probe dispatch, which only the diagnostic
    sync mode pays."""
    import jax

    jax.block_until_ready(fn(*args))       # compile + warm, untimed
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0

