"""The pluggable execution-backend protocol of the superstep runtime.

One :class:`repro.core.runtime.loop.SuperstepRuntime` loop drives every
deployment (DESIGN.md §9); what varies between "one device" and "a shard_map
mesh" is captured here as an :class:`ExecutionBackend`:

  * how the sealed frontier is re-materialised for one superstep
    (device-budget waves vs per-worker cost-balanced slices),
  * how quick patterns are computed and level-1 aggregation is reduced
    (host fold vs psum/OR-allreduce collective),
  * how the expansion itself is dispatched (pilot + stacked-drain chunk
    pipeline vs one sharded program with exact-capacity retries), and
  * what per-step accounting rides on top (compile signatures, collective
    bytes).

Implementations: :class:`repro.core.runtime.serial.SerialBackend` and
:class:`repro.core.runtime.shard.ShardMapBackend`. Both append children to
the shared :class:`repro.core.store.FrontierStore` — sealed stores are the
*only* inter-superstep state, which is exactly what makes the superstep
boundary a checkpointable cut (``runtime/checkpoint.py``).
"""
from __future__ import annotations

import abc
from typing import List, Optional, Tuple

import numpy as np

from repro.core import obs
from repro.core.aggregation import StepAggregates
from repro.core.api import MiningApp
from repro.core.graph import DeviceGraph
from repro.core.runtime.config import RunConfig
from repro.core.stats import RunStats, StepStats
from repro.core.store import FrontierStore


class ExecutionBackend(abc.ABC):
    """One BSP superstep's execution strategy, behind the unified loop."""

    name: str = "base"

    def bind(self, g: DeviceGraph, app: MiningApp,
             config: RunConfig) -> FrontierStore:
        """Attach to one run: build the frontier store and the jitted
        programs this backend dispatches. Returns the store (the runtime
        owns the loop, the backend owns the programs). ``capacity`` is the
        persistent output-capacity bucket — it survives across supersteps
        (one overflow re-dispatch per run, not per step) and is part of the
        checkpoint cursor.

        Every tri-state knob is resolved here, ONCE, through the cost
        model (DESIGN.md §14): ``self.config`` and everything built from
        it see only concrete choices, and ``self.decisions`` carries the
        effective table for ``RunStats``/trace recording."""
        from repro.core.runtime import costmodel

        config, self.decisions = costmodel.resolve(config, g, app, self.name)
        self.g = g
        self.app = app
        self.config = config
        self.capacity = max(config.initial_capacity, 1)
        return self._make_store()

    @abc.abstractmethod
    def _make_store(self) -> FrontierStore:
        """Build the store this backend mines through."""

    # -- one superstep, in loop order --------------------------------------
    @abc.abstractmethod
    def begin_step(self, store: FrontierStore,
                   st: StepStats) -> List[np.ndarray]:
        """Re-materialise the sealed frontier as row blocks: device-budget
        waves (serial) or per-worker slices (shard map)."""

    @abc.abstractmethod
    def quick_codes(
        self, blocks: List[np.ndarray], size: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Quick-pattern ``(codes (B,3) int64, local_verts (B,8) int32)``
        of the materialised frontier — only called when the previous step's
        chunk programs did not carry them."""

    @abc.abstractmethod
    def aggregate(
        self, codes: np.ndarray, lv: np.ndarray, st: StepStats
    ) -> Tuple[StepAggregates, np.ndarray]:
        """Two-level pattern aggregation over the frontier's quick codes.
        Returns ``(aggregates, per-row canonical slot)`` and fills the
        step's pattern/iso/collective counters."""

    def aggregate_step(
        self, blocks: List[np.ndarray], size: int, carried, st: StepStats
    ) -> Tuple[StepAggregates, Optional[np.ndarray]]:
        """One superstep's pattern aggregation, end to end. ``carried`` is
        whatever this backend's :meth:`expand` returned last step (opaque
        to the loop). Returns ``(aggregates, per-row canonical slot)``;
        a ``None`` slot array means level 1 stayed on device (DESIGN.md
        §10) and alpha must be evaluated via ``app.pattern_filter`` +
        :meth:`alpha_rows`. This base implementation is the host reference
        flow: host codes (carried or recomputed) through
        ``aggregation.aggregate_rows``-style :meth:`aggregate`."""
        n_frontier = sum(len(blk) for blk in blocks)
        if (
            isinstance(carried, tuple)
            and len(carried) == 2
            and len(carried[0]) == n_frontier
        ):
            codes, lv = carried
        else:
            with obs.span("aggregate.bin"):
                codes, lv = self.quick_codes(blocks, size)
        obs.count(st, "bytes_to_host", codes.nbytes + lv.nbytes)
        return self.aggregate(codes, lv, st)

    def alpha_rows(self, pk: np.ndarray, st: StepStats) -> np.ndarray:
        """Per-row alpha mask over the materialised frontier, derived from
        the per-pattern verdict ``pk`` ((Pc,) bool) of the device
        aggregation path. Only called when ``pk`` actually prunes."""
        raise NotImplementedError(
            "per-row alpha requires the host aggregation path"
        )

    def prune(self, blocks: List[np.ndarray],
              alpha: np.ndarray) -> List[np.ndarray]:
        """Apply the app's aggregation filter to the materialised blocks
        (the mask spans their concatenation, in order)."""
        off, pruned = 0, []
        for blk in blocks:
            pruned.append(blk[alpha[off: off + len(blk)]])
            off += len(blk)
        return pruned

    @abc.abstractmethod
    def expand(self, store: FrontierStore, blocks: List[np.ndarray],
               size: int, st: StepStats) -> Optional[tuple]:
        """Expand the frontier one size, appending children to ``store``.
        Returns carried ``(codes, local_verts)`` of the children when the
        chunk programs computed them in the same pass (DESIGN.md §8), else
        None."""

    def end_step(self, store: FrontierStore, st: StepStats) -> None:
        """Post-seal accounting hook (e.g. frontier-exchange bytes)."""

    def finalize(self, stats: RunStats) -> None:
        """End-of-run accounting hook (compile signatures etc.)."""
