"""Backend-aware dispatch shared by every Pallas kernel wrapper.

One rule, stated once (the per-kernel ``ops`` wrappers all defer here):

  * ``interpret=None`` (the default everywhere) resolves automatically:
    the kernel *compiles* (Mosaic) on TPU and runs in the Pallas
    *interpreter* everywhere else. The kernels use TPU-only features
    (SMEM operands, Mosaic compiler parameters), so TPU is the one
    compiled target.
  * ``interpret=True`` / ``False`` forces the choice (tests pin ``True``
    so CI on CPU exercises the exact kernel dataflow deterministically).
    On TPU the interpreter is refused: a chip run never silently runs a
    kernel in interpret mode.

:func:`pallas_call` is the one way the kernels build a Pallas call: it
resolves ``interpret`` here and traces the kernel with 32-bit defaults.
``repro.core`` enables x64 globally (quick-pattern codes are 64-bit
keys), and Mosaic rejects the 64-bit index-map constants and iotas x64
would put into a kernel body, so every kernel operand is pinned to a
32-bit dtype by its wrapper.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Set

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: backends with a native Pallas lowering for these kernels.
COMPILED_BACKENDS = ("tpu",)

#: scoped-VMEM limit for kernels that keep a table (bitmap, neighbour
#: table, gather source) resident: two pipeline buffers of an 8 MiB table
#: plus the per-block working set, inside the v5e core's 128 MiB.
RESIDENT_VMEM_BYTES = 48 * 2**20


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Map the tri-state ``interpret`` knob to a concrete bool.

    Raises ``ValueError`` for ``interpret=True`` on TPU."""
    compiled = jax.default_backend() in COMPILED_BACKENDS
    if interpret is None:
        return not compiled
    if interpret and compiled:
        raise ValueError(
            "Pallas interpret mode requested on a TPU backend; the kernels "
            "run compiled there (pallas_interpret must be None or False)"
        )
    return bool(interpret)


#: per-thread stack of route sinks (see :func:`push_route_sink`).
_ROUTE_SINKS = threading.local()


def push_route_sink(sink: Dict[str, Set[str]]) -> None:
    """Collect the kernel routes traced from now on into ``sink``
    (``{kernel: {route, ...}}``) until :func:`pop_route_sink`.

    A route is recorded when a program is *traced*: ``"compiled"`` or
    ``"interpret"`` by :func:`pallas_call` for a kernel that runs, or a
    ``"jnp:<reason>"`` fallback noted by a dispatch wrapper that declined
    its kernel (e.g. a bitmap too large for VMEM). A program reused from
    an earlier trace records nothing again."""
    stack = getattr(_ROUTE_SINKS, "stack", None)
    if stack is None:
        stack = _ROUTE_SINKS.stack = []
    stack.append(sink)


def pop_route_sink(sink: Dict[str, Set[str]]) -> None:
    """Stop collecting into ``sink`` (the innermost sink)."""
    assert _ROUTE_SINKS.stack[-1] is sink
    _ROUTE_SINKS.stack.pop()


def note_route(kernel: str, route: str) -> None:
    """Record that ``kernel`` took ``route`` in the program being traced."""
    for sink in getattr(_ROUTE_SINKS, "stack", ()):
        sink.setdefault(kernel, set()).add(route)


def pallas_call(kernel, *, name: str, interpret=None, vmem_limit_bytes=None,
                **kwargs):
    """``pl.pallas_call`` with ``interpret`` resolved by
    :func:`resolve_interpret` and the kernel traced with x64 off; the
    route (``name`` -> compiled / interpret) is noted for the run's
    record (:func:`push_route_sink`).

    ``vmem_limit_bytes`` raises Mosaic's scoped-VMEM limit for kernels
    that keep a table resident (ignored by the interpreter)."""
    itp = resolve_interpret(interpret)
    if vmem_limit_bytes is not None and not itp:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=int(vmem_limit_bytes)
        )
    call = pl.pallas_call(kernel, interpret=itp, name=name, **kwargs)

    def run(*args):
        note_route(name, "interpret" if itp else "compiled")
        with jax.enable_x64(False):
            return call(*args)

    return run


#: halo-exchange strategies of the partitioned layout (DESIGN.md §11).
HALO_STRATEGIES = ("alltoall", "gather")


def resolve_halo(halo: Optional[str] = None) -> str:
    """Map the halo knob (``RunConfig.halo``) to a concrete strategy.

    ``None``/``"auto"`` -> ``"alltoall"``: the position-aligned
    request/response all-to-all ships only the rows each worker actually
    asked for (O(halo) per worker). ``"gather"`` is the ragged fallback —
    every worker all-gathers the full shard tables (O(n) per worker), kept
    for meshes whose all-to-all lowering is unavailable and as the
    equivalence oracle."""
    if halo is None or halo == "auto":
        return "alltoall"
    if halo not in HALO_STRATEGIES:
        raise ValueError(
            f"unknown halo strategy {halo!r} (expected one of "
            f"{HALO_STRATEGIES} or 'auto')"
        )
    return halo


#: level-2 canonicalisation placements (DESIGN.md §15).
CANONICAL_PLACEMENTS = ("device", "host", "host_async")


def resolve_canonical_placement(placement: Optional[str] = None) -> str:
    """Map the level-2 placement knob (``RunConfig.canonical_placement``)
    to a concrete choice.

    ``None``/``"auto"`` -> ``"host"``: the memoised host batch is the
    reference placement and the static pre-calibration default — the cost
    model (``costmodel.resolve``) replaces it with the measured-fastest of
    ``"device"`` (batched permutation-refinement kernel,
    ``kernels/canonical_refine.py``) and ``"host_async"`` (background
    thread joined at the seal boundary) when calibration runs."""
    if placement is None or placement == "auto":
        return "host"
    if placement not in CANONICAL_PLACEMENTS:
        raise ValueError(
            f"unknown canonical placement {placement!r} (expected one of "
            f"{CANONICAL_PLACEMENTS} or 'auto')"
        )
    return placement


def device_scope(name: str):
    """Named XLA scope for a device-program stage (``repro/<name>``):
    the device-side half of the §12 span taxonomy. ``jax.named_scope``
    only relabels operations during tracing — zero runtime cost — so the
    fused-chunk / halo-exchange / aggregation-bin stages are ALWAYS
    scoped, and a ``jax.profiler.trace`` capture lines its device slices
    up with the host spans (``obs.annotate``) without recompiling."""
    return jax.named_scope(f"repro/{name}")

# NB: the old ``default_use_pallas`` static heuristic moved into the
# cost-model layer (``runtime/costmodel.py``): ``static_table`` keeps its
# TPU-only rule as the pre-calibration default, and calibration replaces
# it with a measured choice per backend (DESIGN.md §14).
