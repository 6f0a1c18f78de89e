"""Halo-tile gather for the partitioned graph layout (DESIGN.md §11).

The partitioned engine never walks the whole graph: each chunk program
first derives its *halo* — the ascending unique set of member vertices
(vertex mode) or member-edge endpoints (edge mode) whose neighbour /
adjacency rows the chunk will touch — and then gathers exactly those rows
out of the shard-stacked tables into a dense tile the rest of the fused
pipeline consumes (``explore.build_tile_view``).

Two pieces, same dispatch idioms as ``compact.py``:

  * :func:`halo_unique` — presence-bitmap scatter + stream compaction.
    The compaction reuses ``kernels/compact.py`` verbatim (kernel or jnp
    ref), so it inherits THE unclamped-count contract: ``count`` is the
    true number of distinct vertices even when it exceeds ``cap``. The
    engine sizes ``cap`` from static chunk shapes (``next_pow2(min(slots,
    n))``), which makes overflow impossible by construction — the
    unclamped count still rides the outputs so callers can assert it.
    Pad slots hold the sentinel ``n`` (one past the last vertex id), which
    keeps the tile *ascending* — rank translation in the tile view is a
    single ``searchsorted``.
  * :func:`gather_rows` — the Pallas kernel: the shard-stacked table
    is kept **VMEM-resident** (same residency pattern as the
    canonical-check bitmap) and each grid step copies one block of halo
    rows out of it, one dynamic-offset row load per row id read from
    SMEM (Mosaic has no in-kernel vector gather). ``gather_rows_ref`` is the jnp route with the exact
    same contract; :func:`fits_vmem` guards the residency, larger tables
    fall back to the jnp gather streamed from HBM.

Out-of-range row ids (the sentinel pad, or any negative id) produce
``fill``-valued rows in both routes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compact as compact_lib
from repro.kernels.dispatch import RESIDENT_VMEM_BYTES, note_route, pallas_call

#: bytes of gathered-from table we allow resident in VMEM; larger tables
#: route to the jnp gather (streamed from HBM by XLA) — same budget shape
#: as the canonical-check bitmap limit.
VMEM_TABLE_LIMIT = 8 * 2**20
#: rows gathered per grid step: one 1-D SMEM tile of row ids.
ROW_BLOCK = 1024


def fits_vmem(table) -> bool:
    """True when the (rows, R) source table is VMEM-resident-sized."""
    return table.size * table.dtype.itemsize <= VMEM_TABLE_LIMIT


def _gather_kernel(rows_ref, table_ref, out_ref):
    """One grid step: copy a block of table rows. ``rows_ref`` is the
    block's row ids in SMEM; the source table uses a constant index map,
    so it stays VMEM-resident across the grid and each row is one
    dynamic-offset row load."""
    def body(j, carry):
        out_ref[pl.ds(j, 1), :] = table_ref[pl.ds(rows_ref[j], 1), :]
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gather_rows_pallas(table, rows, block: int = ROW_BLOCK, interpret=None):
    """table (N, R) of a 32-bit dtype; rows (U,) int32 -> (U, R) =
    table[clip(rows)], no masking (callers apply the fill; see
    :func:`gather_rows`). Any ``U`` accepted — padded internally to a
    multiple of ``block`` (rounded up to :data:`ROW_BLOCK`, the 1-D SMEM
    tile) and sliced back."""
    u = rows.shape[0]
    n, r = table.shape
    if u == 0:
        return jnp.zeros((0, r), table.dtype)
    block = -(-max(int(block), 1) // ROW_BLOCK) * ROW_BLOCK
    pad = (-u) % block
    rows = jnp.clip(rows.astype(jnp.int32), 0, n - 1)
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad,), jnp.int32)])
    src = jax.lax.bitcast_convert_type(table, jnp.int32)

    out = pallas_call(
        _gather_kernel,
        name="gather_rows",
        grid=((u + pad) // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((n, r), lambda i: (0, 0)),   # table VMEM-resident
        ],
        out_specs=pl.BlockSpec((block, r), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((u + pad, r), jnp.int32),
        vmem_limit_bytes=RESIDENT_VMEM_BYTES,
        interpret=interpret,
    )(rows, src)
    return jax.lax.bitcast_convert_type(out[:u], table.dtype)


def gather_rows_ref(table, rows):
    """The jnp route (clipped take) with the kernel's exact contract."""
    return table[jnp.clip(rows, 0, table.shape[0] - 1)]


def gather_rows(table, rows, fill, *, use_kernel: bool = False,
                interpret=None):
    """Gather ``table[rows]`` with out-of-range rows replaced by ``fill``.

    ``use_kernel`` routes through the VMEM-resident Pallas gather when the
    table fits (:func:`fits_vmem`); otherwise — and always on the ref path —
    XLA's HBM-streamed take runs. Both routes return identical values."""
    if use_kernel and fits_vmem(table):
        out = gather_rows_pallas(table, rows, interpret=interpret)
    else:
        if use_kernel:
            note_route("gather_rows", "jnp:table-exceeds-vmem")
        out = gather_rows_ref(table, rows)
    ok = (rows >= 0) & (rows < table.shape[0])
    return jnp.where(ok[:, None], out, jnp.asarray(fill, table.dtype))


def halo_unique(verts, n: int, cap: int, *, use_kernel: bool = False,
                interpret=None):
    """Ascending distinct vertex ids of ``verts`` (invalid ids < 0 or >= n
    ignored), padded with the sentinel ``n``.

    Returns ``(uniq (cap,) int32 ascending, count () int32)`` where
    ``count`` is the UNCLAMPED distinct total (same overflow contract as
    ``compact.py`` — detection is a pure host decision; the engine's
    static ``cap = next_pow2(min(slots, n))`` bound makes it impossible on
    the hot path). The presence scatter is one ``.at[].set`` over an
    ``(n + 1,)`` bitmap; the compaction is ``kernels/compact.py``."""
    verts = jnp.asarray(verts).reshape(-1)
    ok = (verts >= 0) & (verts < n)
    slot = jnp.where(ok, verts, n)
    presence = jnp.zeros((n + 1,), bool).at[slot].set(True)[:n]
    if use_kernel:
        idx, count = compact_lib.stream_compact_pallas(
            presence, cap, interpret=interpret
        )
    else:
        idx, count = compact_lib.stream_compact_ref(presence, cap)
    valid = jnp.arange(cap) < jnp.minimum(count, cap)
    uniq = jnp.where(valid, idx, n).astype(jnp.int32)
    return uniq, count
