"""Distributed mining entry point — a thin wrapper over the unified runtime.

The shard-map superstep this module used to implement (paper §5.1/§5.3 as
one jitted ``shard_map`` program per exploration step, with the two-level
aggregation collective and the §5.2 DenseODAG OR-merge exchange) now lives
ONCE in :mod:`repro.core.runtime.shard` behind the
:class:`~repro.core.runtime.backend.ExecutionBackend` protocol; the BSP
loop around it is the same :class:`~repro.core.runtime.SuperstepRuntime`
the serial engine drives. ``run_distributed`` and ``DistConfig`` are kept
as the stable public names — ``DistConfig`` is a deprecation shim over
:class:`RunConfig` (the shard-map backend reads ``axes`` /
``naive_aggregation`` from it and ignores the serial-only knobs).

``run_distributed`` mirrors ``engine.run`` and must produce identical
results (integration-tested); ``mining_step_for_dryrun`` is the fixed-shape
program the multi-pod dry-run lowers on the 512-chip mesh.

Checkpoint/resume (DESIGN.md §9): ``DistConfig(checkpoint_dir=...)``
persists every sealed superstep; resuming with a mesh of a *different*
worker count is elastic by construction — per-worker slices are
re-partitioned from the restored store at extraction time.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import explore, pattern as pattern_lib
from repro.core.api import MiningApp
from repro.core.graph import DeviceGraph, Graph
from repro.core.runtime import (
    MiningResult,
    RunConfig,
    ShardMapBackend,
    SuperstepRuntime,
)
from repro.core.runtime.shard import (  # noqa: F401  (canonical home)
    make_sharded_aggregate,
    make_sharded_expand,
    mesh_axis_size as _mesh_axis_size,
    pad_parts,
    partition_frontier,
    shard_map,
)
__all__ = ["DistConfig", "run_distributed", "mining_step_for_dryrun"]


@dataclasses.dataclass
class DistConfig(RunConfig):
    """Deprecated alias of :class:`repro.core.runtime.RunConfig`.

    Kept as an empty subclass so every pre-runtime call site (and kwarg)
    keeps working; new code should construct ``RunConfig`` directly."""


def run_distributed(
    graph: Graph | DeviceGraph,
    app: MiningApp,
    mesh: Mesh,
    config: Optional[RunConfig] = None,
) -> MiningResult:
    """Mine ``graph`` with ``app`` sharded over ``mesh`` (same
    ``MiningResult`` contract as ``engine.run``)."""
    return SuperstepRuntime(graph, app, config, ShardMapBackend(mesh)).run()


# ---------------------------------------------------------------------------
# Fixed-shape mining step for the multi-pod dry-run
# ---------------------------------------------------------------------------

def mining_step_for_dryrun(mesh: Mesh, axes=("pod", "data"),
                           use_pallas: Optional[bool] = None, interpret=None):
    """A single fully fixed-shape distributed exploration step suitable for
    AOT lowering on the production mesh: expand + canonicality + quick
    patterns + domain-bitmap psum. Pattern dictionary capacity is static.

    ``use_pallas=None`` resolves against the *lowering host's* backend
    (same rule as the engines). NB: the AOT dry-run harness forces CPU
    host devices, so it models the jnp check path by default — pass
    ``use_pallas=True`` explicitly to lower/inspect the kernel path the
    TPU engine defaults to.
    """
    resolved_pallas = RunConfig(use_pallas=use_pallas).resolve_use_pallas()

    def step(g: DeviceGraph, members, n_valid, quick_dict):
        """members: (B, k) sharded over `axes`; quick_dict: (Q, 3) replicated."""

        def worker(g, quick_dict, members, n_valid):
            m, nv = members[0], n_valid[0]
            exp = explore.expand_vertex(
                g, m, nv, use_pallas=resolved_pallas, interpret=interpret
            )
            out_cap = m.shape[0]  # fixed children capacity = shard size
            children, count = explore.compact(m, exp, exp.keep, out_cap)
            child_nv = jnp.where(
                jnp.arange(out_cap) < count, jnp.max(nv) + 1, 0
            ).astype(jnp.int32)
            qp = pattern_lib.quick_pattern_vertex(g, children, child_nv)
            # static-capacity dictionary match (searchsorted on w0 then
            # verify all three words)
            q = quick_dict.shape[0]
            eq = (qp.codes[:, None, :] == quick_dict[None, :, :]).all(-1)
            slot = jnp.where(eq.any(1), jnp.argmax(eq, axis=1), q)
            counts = jax.ops.segment_sum(
                (child_nv > 0).astype(jnp.int32), slot, q + 1
            )[:q]
            counts = jax.lax.psum(counts, axes)
            return children[None], count[None], counts[None]

        spec = P(axes)
        return shard_map(
            functools.partial(worker, g, quick_dict),
            mesh=mesh,
            in_specs=(spec, spec),
            out_specs=(spec, spec, spec),
        )(members, n_valid)

    return step
