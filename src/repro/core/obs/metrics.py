"""Metrics registry + the single write path for StepStats counters.

Every ``st.bytes_to_host += ...`` / ``st.n_host_syncs += 1`` site the
backends grew now routes through :func:`count` / :func:`set_stat`, which

  * perform **exactly** the arithmetic the inline mutation did
    (``setattr(st, name, getattr(st, name) + value)``), so every existing
    bench gate built on ``StepStats`` stays bit-identical whether or not
    anything is observing, and
  * mirror the update into the installed :class:`MetricsRegistry` (when
    one is installed) as named counters/gauges — the machine-readable
    stream the exporters render.

The disabled path is one module-level read + the unchanged setattr.

Two more always-on records live here: :func:`to_host`, the counted
blocking device→host read behind ``StepStats.n_device_reads``, and
:func:`totals`, the process's cumulative counters over every completed
run — what a long-lived mining process exposes for scraping.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np


class MetricsRegistry:
    """Named counters and gauges for one traced run.

    ``counters``  accumulate (run totals per name);
    ``gauges``    keep the last value and the max watermark.
    Thread-safe — same contract as the tracer.
    """

    def __init__(self) -> None:
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.gauge_max: Dict[str, float] = {}
        #: per-step counter history: name -> [(step, value), ...]
        self.by_step: Dict[str, List[Tuple[int, float]]] = {}
        self._lock = threading.Lock()

    def count(self, name: str, value, step: Optional[int] = None) -> None:
        v = float(value)
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + v
            if step is not None:
                self.by_step.setdefault(name, []).append((int(step), v))

    def gauge(self, name: str, value, step: Optional[int] = None) -> None:
        v = float(value)
        with self._lock:
            self.gauges[name] = v
            if v > self.gauge_max.get(name, float("-inf")):
                self.gauge_max[name] = v
            if step is not None:
                self.by_step.setdefault(name, []).append((int(step), v))

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "gauge_max": dict(self.gauge_max),
            }


_REGISTRY: Optional[MetricsRegistry] = None


def install(registry: Optional[MetricsRegistry]) -> None:
    global _REGISTRY
    _REGISTRY = registry


def current() -> Optional[MetricsRegistry]:
    return _REGISTRY


def count(st, name: str, value) -> None:
    """THE counter write path: ``st.<name> += value``, bit-identical to the
    inline mutation it replaced, mirrored into the registry when one is
    installed."""
    setattr(st, name, getattr(st, name) + value)
    reg = _REGISTRY
    if reg is not None:
        reg.count(name, value, step=getattr(st, "step", None))


def set_stat(st, name: str, value) -> None:
    """Assignment-style stats (``st.<name> = value``) through the same
    observation funnel."""
    setattr(st, name, value)
    reg = _REGISTRY
    if reg is not None:
        reg.gauge(name, value, step=getattr(st, "step", None))


def gauge(name: str, value, step: Optional[int] = None) -> None:
    """Registry-only gauge (no StepStats field) — e.g. device memory."""
    reg = _REGISTRY
    if reg is not None:
        reg.gauge(name, value, step=step)


def sample_device_memory() -> Optional[int]:
    """Device bytes-in-use of the default device, or None where the
    backend exposes no allocator stats (CPU jax commonly doesn't). Never
    raises and never syncs — ``memory_stats`` reads allocator counters."""
    try:
        import jax

        stats = jax.devices()[0].memory_stats()
        if not stats:
            return None
        v = stats.get("bytes_in_use")
        return int(v) if v is not None else None
    except Exception:
        return None


# -- counted device reads -----------------------------------------------------

_READS = threading.local()


def to_host(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)`` of a device value: one blocking
    device→host read, tallied on the calling thread (:func:`read_count`).
    The serial backend charges the tally's growth over each of its hooks
    to ``StepStats.n_device_reads``."""
    _READS.n = getattr(_READS, "n", 0) + 1
    return np.asarray(x, dtype=dtype)


def read_count() -> int:
    """Device reads :func:`to_host` made on this thread so far."""
    return getattr(_READS, "n", 0)


# -- process totals -----------------------------------------------------------

_TOTALS: Dict[str, int] = {}
_TOTALS_LOCK = threading.Lock()


def _step_counters(st) -> Tuple[str, ...]:
    """The integer counters of ``StepStats`` (its ``step`` and ``size``
    are coordinates, not counts)."""
    return tuple(
        f.name for f in dataclasses.fields(st)
        if f.type in (int, "int") and f.name not in ("step", "size")
    )


def fold_run(steps) -> None:
    """Add one completed run's supersteps to the process totals: every
    integer ``StepStats`` counter, plus ``runs`` and ``supersteps``.
    Called once per run by the runtime loop, traced or not."""
    add: Dict[str, int] = {"runs": 1, "supersteps": len(steps)}
    for st in steps:
        for name in _step_counters(st):
            add[name] = add.get(name, 0) + int(getattr(st, name))
    with _TOTALS_LOCK:
        for k, v in add.items():
            _TOTALS[k] = _TOTALS.get(k, 0) + v


def totals() -> Dict[str, int]:
    """Cumulative counters of every run this process completed, for an
    operator to scrape: ``runs``, ``supersteps`` and the sum of each
    integer ``StepStats`` counter (``n_generated``, ``n_lanes``,
    ``n_host_syncs``, ``n_device_reads``, ``rows_to_host``,
    ``bytes_to_host``, ...). A copy; never reset."""
    with _TOTALS_LOCK:
        return dict(_TOTALS)
