"""Stream compaction (block prefix sum + scatter) with a Pallas scan.

The chunk program of the fused superstep pipeline (DESIGN.md §8) must turn
a flat keep mask over candidate slots into the dense child frontier. The
jnp route is ``jnp.nonzero(keep, size=out_cap, fill_value=0)``. The kernel
route splits the classic stream-compaction dataflow in two:

  * :func:`prefix_sum_pallas` — the scan, as a Pallas kernel. The flags
    are laid out lane-dense as ``(rows, 128)``; each grid step takes a
    ``(block_rows, 128)`` tile, computes its inclusive prefix sum with two
    MXU matmuls (row-wise against an upper-triangular ones block, then the
    row totals against a strictly-lower-triangular block — exact, since
    every partial sum is a small integer), and adds the running total
    carried across the sequential grid in a revisited output tile. Mosaic
    has no ``cumsum`` lowering; the matmul scan is what it lowers.
  * the scatter of kept source indices to their output positions, done by
    XLA (Mosaic has no vector scatter either).

The same scan serves the level-1 segment-unique of ``kernels/aggregate.py``.

Contract (identical to the jnp route, so the two are interchangeable
inside one jitted chunk program):

  * ``idx[:count]`` are the kept positions in ascending order; slots past
    ``count`` hold 0 (the callers mask them out via ``count``).
  * ``count`` is the TOTAL number of kept slots, *not* clamped to
    ``out_cap`` — overflow detection stays a pure host decision on the
    already-drained count, which is what keeps the fused engine's retry
    path sync-free (``repro.core.runtime.serial``).

Dispatch follows the shared rules in :mod:`repro.kernels.dispatch`:
``interpret=None`` compiles on TPU and interprets elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import pallas_call

LANES = 128
#: sublane multiple of a 32-bit tile: blocks are whole (8, 128) tiles.
SUBLANES = 8


def _prefix_kernel(flags_ref, incl_ref, carry_ref):
    """One grid step: inclusive prefix sum of a (rows, 128) 0/1 tile in
    row-major order, offset by the total carried from earlier steps.

    ``carry_ref`` uses a constant index map, so the same (8, 128) tile is
    revisited by every (sequential) grid step; every element holds the
    running total."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.zeros(carry_ref.shape, jnp.int32)

    x = flags_ref[...].astype(jnp.bfloat16)                 # (R, 128) 0/1
    rows = x.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    upper = (r <= c).astype(jnp.bfloat16)
    # within-row inclusive prefix (each entry <= 128, exact)
    within = jnp.dot(x, upper, preferred_element_type=jnp.float32)
    totals = jnp.broadcast_to(within[:, LANES - 1:LANES], (rows, LANES))
    rr = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    rc = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    lower = (rc < rr).astype(jnp.bfloat16)
    # exclusive prefix of the row totals (each total <= 128 is exact in
    # bf16; the f32 sums stay below 2^24)
    before = jnp.dot(lower, totals.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    base = carry_ref[0:1, :]
    incl_ref[...] = base + (within + before).astype(jnp.int32)
    block_total = (before[rows - 1:rows, :]
                   + totals[rows - 1:rows, :]).astype(jnp.int32)
    carry_ref[...] = jnp.broadcast_to(base + block_total, carry_ref.shape)


def _block_rows(block: int) -> int:
    """Rows of 128 lanes per grid step for a requested ``block`` of
    elements: whole (8, 128) tiles, at least one."""
    rows = -(-max(int(block), 1) // LANES)
    return -(-rows // SUBLANES) * SUBLANES


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def prefix_sum_pallas(flags, block: int = 32768, interpret=None):
    """flags (B,) bool -> (incl (B,) int32, total () int32): the inclusive
    prefix sum of ``flags`` and its total. Any ``B`` including 0."""
    n = flags.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32)
    rows = _block_rows(min(block, n))
    per_block = rows * LANES
    pad = (-n) % per_block
    x = jnp.concatenate(
        [flags.astype(jnp.int32), jnp.zeros((pad,), jnp.int32)]
    ).reshape(-1, LANES)
    incl, carry = pallas_call(
        _prefix_kernel,
        name="prefix_sum",
        grid=(x.shape[0] // rows,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda i: (0, 0)),  # carry
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, jnp.int32),
            jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(x)
    return incl.reshape(-1)[:n], carry[0, 0]


@functools.partial(jax.jit, static_argnames=("out_cap", "block", "interpret"))
def stream_compact_pallas(keep, out_cap: int, block: int = 32768,
                          interpret=None):
    """keep (B,) bool -> (idx (out_cap,) int32, count () int32).

    ``idx[:min(count, out_cap)]`` are the kept positions of ``keep`` in
    ascending order (pad slots 0); ``count`` is the unclamped kept total.
    Accepts any ``B`` including 0.
    """
    n = keep.shape[0]
    if n == 0:
        return jnp.zeros((out_cap,), jnp.int32), jnp.zeros((), jnp.int32)
    incl, count = prefix_sum_pallas(keep, block=block, interpret=interpret)
    pos = jnp.where(keep, incl - 1, out_cap)
    idx = jnp.zeros((out_cap,), jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop"
    )
    return idx, count


def stream_compact_ref(keep, out_cap: int):
    """The jnp route (nonzero gather) with the kernel's exact contract —
    the fallback `explore.compact` uses when the kernel is off."""
    count = keep.sum().astype(jnp.int32)
    (idx,) = jnp.nonzero(keep, size=out_cap, fill_value=0)
    return idx.astype(jnp.int32), count
