"""Embedding-canonicality check (paper Alg. 2) as Pallas TPU kernels.

The hot inner loop of exploration: millions of (parent, candidate) pairs per
step, each needing k adjacency-bit lookups plus the prefix-order test. The
kernels tile candidates into VMEM blocks and keep the *whole packed
adjacency bitmap resident in VMEM* (graphs up to ~8k vertices: N*N/8 bytes
<= 8 MB), so every adjacency query reads VMEM instead of HBM.

Mosaic has no in-kernel vector gather, so adjacency lookups are built from
what it lowers: the row ids of a block are read from SMEM and each bitmap
row is one dynamic-offset row load into a VMEM scratch; the word of a
row is then picked by a lane compare-select (canonical check) or a
within-vreg lane gather (``take_along_axis``, fused expansion). Every
operand is 32-bit (the uint32 bitmap is bitcast to int32; shifts are
logical) and every 1-D operand travels as a 2-D column.

Two kernels:

  * :func:`canonical_check_tiles_pallas` — the Alg.-2 check over a flat
    batch of (members, cand) pairs, with adjacency read at the members'
    row ids ``ranks`` of a bitmap tile; :func:`canonical_check_pallas` is
    the replicated-bitmap case (``ranks = members``). Batches of any size
    are accepted: the wrapper pads to a block multiple internally (pad
    rows have ``n_valid=0`` / ``cand=-1`` and are sliced off the output).
  * :func:`expand_canonical_pallas` — the *fused* expansion kernel: for a
    block of parent embeddings it enumerates every neighbour-table
    candidate, evaluates slot validity / is-member / first-occurrence dedup
    *and* the Alg.-2 check in one VMEM pass over the packed bitmap. The
    member↔candidate adjacency is computed once and reused by both
    the dedup rule and the canonicality test, eliminating the ``(C, k, k,
    D)`` boolean intermediate that the unfused path materialises in HBM
    through ``g.is_edge``.

``interpret=None`` auto-selects compiled vs interpreter per backend (see
``repro.kernels.dispatch``). Graph-size dispatch (VMEM limits, jnp
fallback) lives in ``ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import RESIDENT_VMEM_BYTES, pallas_call

WORD_BITS = 32
LANES = 128
#: 1-D SMEM operands are tiled in 1024-word units.
SMEM_TILE = 1024


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def _smem_blocks(ids_t, block: int):
    """Lay a (k, B) int32 id table out for per-block SMEM reads: block
    ``i`` occupies the 1-D slice ``[i*S, i*S + k*block)`` of the result,
    entry ``j*block + b`` holding ``ids_t[j, i*block + b]``, with ``S``
    rounded up to the SMEM tile. Negative ids become row 0 (the kernels
    mask them in the vector domain)."""
    k, b = ids_t.shape
    nb = b // block
    s = _round_up(block * k, SMEM_TILE)
    flat = jnp.maximum(ids_t, 0).reshape(k, nb, block).transpose(1, 0, 2)
    flat = jnp.pad(flat.reshape(nb, k * block), ((0, 0), (0, s - k * block)))
    return flat.reshape(-1), s


def _pad_rows(x, rows: int, fill):
    pad = rows - x.shape[0]
    if pad == 0:
        return x
    widths = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def _words(adj_bits, width=None):
    """The packed bitmap as int32 words, optionally right-padded to
    ``width`` words (pad words are 0: no adjacency)."""
    words = jax.lax.bitcast_convert_type(adj_bits, jnp.int32)
    if width is not None and width > words.shape[1]:
        words = jnp.pad(words, ((0, 0), (0, width - words.shape[1])))
    return words


def _false(shape):
    """An all-False mask built as a compare (Mosaic cannot truncate a bool
    constant from its 8-bit storage form)."""
    return jnp.zeros(shape, jnp.int32) != 0


def _bit(word, col):
    """Bit ``col % 32`` of int32 ``word`` as a bool (logical shift)."""
    return (jax.lax.shift_right_logical(word, col & (WORD_BITS - 1)) & 1) == 1


def _tiles_kernel(ranks_smem, rows_ref, adj_ref, out_ref, adj_rows, *, k):
    """Alg. 2 over a block of TB (members, cand) pairs, batch on lanes.

    ``rows_ref`` is an (R, TB) int32 tile: rows ``[0, k)`` the members'
    global ids, ``[k, 2k)`` their bitmap row ids ``ranks``, then
    ``n_valid`` and ``cand``. Adjacency is read at the ranks (SMEM copy
    for the row loads) while the order tests compare global member ids —
    the replicated bitmap is the case ranks == members, which is exactly
    what a partitioned tile cannot assume. The staged bitmap rows are
    transposed once per member so the word pick is a sublane select that
    lands lane-dense."""
    tb = out_ref.shape[1]
    tile = rows_ref[...]
    nvalid = tile[2 * k:2 * k + 1]              # (1, TB)
    cand = tile[2 * k + 1:2 * k + 2]            # (1, TB) global ids
    safe_c = jnp.maximum(cand, 0)
    word_idx = jax.lax.shift_right_logical(safe_c, 5)
    sub = jax.lax.broadcasted_iota(
        jnp.int32, (adj_rows.shape[1], tb), 0
    )

    found = _false((1, tb))
    violation = _false((1, tb))
    for j in range(k):
        def load_row(b, carry, j=j):
            r = ranks_smem[j * tb + b]
            adj_rows[pl.ds(b, 1), :] = adj_ref[pl.ds(r, 1), :]
            return carry

        jax.lax.fori_loop(0, tb, load_row, 0)
        word = jnp.sum(jnp.where(sub == word_idx, adj_rows[...].T, 0),
                       axis=0, keepdims=True)   # (1, TB)
        mj = tile[j:j + 1]
        valid = j < nvalid
        neigh = (_bit(word, safe_c) & valid & (mj >= 0)
                 & (tile[k + j:k + j + 1] >= 0) & (cand >= 0))
        # Alg. 2: a member after the first neighbour must precede cand
        violation = violation | (valid & found & (mj > cand))
        found = found | neigh
    first_ok = (nvalid <= 0) | (tile[0:1] < cand)
    out_ref[...] = jnp.broadcast_to(
        (first_ok & ~violation).astype(jnp.int32), out_ref.shape
    )


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def canonical_check_tiles_pallas(members, ranks, n_valid, cand, adj_tile,
                                 block_b=1024, interpret=None):
    """Tile-indexed Alg.-2 check: members/ranks (B, k) int32, n_valid (B,),
    cand (B,) global ids, adj_tile (U, W) uint32 bitmap rows (``ranks``
    index ``adj_tile``; ranks < 0 read as non-adjacent). Returns (B,)
    bool; any ``B`` accepted (``block_b`` is rounded up to a multiple of
    128: the batch runs along lanes)."""
    b, k = members.shape
    u, w = adj_tile.shape
    if b == 0:
        return jnp.zeros((0,), jnp.bool_)
    block = _round_up(min(block_b, b), LANES)
    bp = _round_up(b, block)
    ranks_t = _pad_rows(ranks.astype(jnp.int32), bp, -1).T
    table = jnp.concatenate([
        _pad_rows(members.astype(jnp.int32), bp, -1).T,
        ranks_t,
        _pad_rows(n_valid.astype(jnp.int32), bp, 0)[None, :],
        _pad_rows(cand.astype(jnp.int32), bp, -1)[None, :],
    ], axis=0)                                  # (2k + 2, Bp)
    nrows = _round_up(2 * k + 2, 8)
    table = jnp.pad(table, ((0, nrows - 2 * k - 2), (0, 0)))
    ranks_flat, s = _smem_blocks(ranks_t, block)
    wp = _round_up(w, LANES)

    out = pallas_call(
        functools.partial(_tiles_kernel, k=k),
        name="canonical_check",
        grid=(bp // block,),
        in_specs=[
            pl.BlockSpec((s,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((nrows, block), lambda i: (0, i)),
            pl.BlockSpec((u, wp), lambda i: (0, 0)),  # bitmap VMEM-resident
        ],
        out_specs=pl.BlockSpec((8, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, bp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block, wp), jnp.int32)],
        vmem_limit_bytes=RESIDENT_VMEM_BYTES,
        interpret=interpret,
    )(ranks_flat, table, _words(adj_tile, wp))
    return out[0, :b] == 1


def canonical_check_pallas(members, n_valid, cand, adj_bits, block_b=1024,
                           interpret=None):
    """members (B,k) int32; n_valid (B,); cand (B,); adj_bits (N,W) uint32.
    Returns (B,) bool — True iff members[:n_valid]+[cand] is canonical.

    The replicated bitmap is indexed by vertex id, so this is the tiled
    check with ``ranks = members``. Handles any batch size ``B``
    (including 0 and non-multiples of ``block_b``).
    """
    return canonical_check_tiles_pallas(
        members, members, n_valid, cand, adj_bits,
        block_b=block_b, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Fused expansion + canonicality kernel
# ---------------------------------------------------------------------------

def _lane_gather(rows, idx):
    """``out[t, d] = rows[t, idx[t, d]]`` for a (TC, Wp) int32 table and a
    (TC, 128) index tile with ``0 <= idx < Wp``: one within-vreg lane
    gather per 128-word slice of ``rows``."""
    out = jnp.zeros(idx.shape, jnp.int32)
    for q in range(rows.shape[1] // LANES):
        local = idx - q * LANES
        hit = (local >= 0) & (local < LANES)
        got = jnp.take_along_axis(
            rows[:, q * LANES:(q + 1) * LANES],
            jnp.clip(local, 0, LANES - 1), axis=1,
        )
        out = jnp.where(hit, got, out)
    return out


def _expand_kernel(members_smem, members_ref, nvalid_ref, nbr_ref, adj_ref,
                   cand_ref, valid_ref, keep_ref, nbr_rows, adj_rows):
    """One block of TC parents. Row loads (ids from SMEM) stage every
    member's neighbour row and bitmap row in VMEM scratch; the candidate
    planes are then processed 128 lanes at a time: candidate ids, slot
    validity, is-member, first-occurrence dedup and Alg. 2, with the
    member↔candidate adjacency computed once per (member, slot) pair."""
    tc, k = members_ref.shape
    dp = nbr_ref.shape[1]
    members = members_ref[...]                  # (TC, k)
    nvalid = nvalid_ref[...]                    # (TC, 1)

    for m in range(k):
        def load_rows(b, carry, m=m):
            v = members_smem[m * tc + b]
            nbr_rows[m, pl.ds(b, 1), :] = nbr_ref[pl.ds(v, 1), :]
            adj_rows[m, pl.ds(b, 1), :] = adj_ref[pl.ds(v, 1), :]
            return carry

        jax.lax.fori_loop(0, tc, load_rows, 0)

    member_ok = [m < nvalid for m in range(k)]              # (TC, 1) each
    mcol = [members[:, m:m + 1] for m in range(k)]
    first = members[:, 0:1]
    for i in range(k):
        for c0 in range(0, dp, LANES):
            nb = nbr_rows[i, :, c0:c0 + LANES]                 # (TC, 128)
            cand = jnp.where(member_ok[i], nb, -1)
            slot_ok = cand >= 0
            safe_c = jnp.maximum(cand, 0)
            word_idx = jax.lax.shift_right_logical(safe_c, 5)
            is_member = _false(cand.shape)
            seen_earlier = _false(cand.shape)
            found = _false(cand.shape)
            violation = _false(cand.shape)
            for m in range(k):
                is_member = is_member | (cand == mcol[m])
                word = _lane_gather(adj_rows[m], word_idx)
                adj_mc = _bit(word, safe_c) & member_ok[m] & slot_ok
                if m < i:
                    seen_earlier = seen_earlier | adj_mc
                violation = violation | (
                    member_ok[m] & found & (mcol[m] > cand)
                )
                found = found | adj_mc
            valid = slot_ok & ~is_member & ~seen_earlier
            first_ok = (nvalid <= 0) | (first < cand)
            canon = first_ok & ~violation
            cand_ref[i, :, c0:c0 + LANES] = cand
            valid_ref[i, :, c0:c0 + LANES] = valid.astype(jnp.int32)
            keep_ref[i, :, c0:c0 + LANES] = (valid & canon).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def expand_canonical_pallas(members, n_valid, nbr, adj_bits, block_c=64,
                            interpret=None):
    """Fused vertex expansion: members (C,k) int32, n_valid (C,),
    nbr (N,D) int32 padded neighbour table, adj_bits (N,W) uint32.

    Returns ``(cand, valid, keep)``, each ``(C, k, D)``: the candidate
    vertex per slot, the pre-canonicality validity mask (slot-ok &
    not-member & first-occurrence) and the final keep mask (valid &
    Alg.-2 canonical). Any ``C`` is accepted (padded internally;
    ``block_c`` is rounded up to a multiple of 8).
    """
    c, k = members.shape
    n, d = nbr.shape
    if c == 0:
        z = jnp.zeros((0, k, d), jnp.int32)
        return z, z.astype(bool), z.astype(bool)
    block = _round_up(min(block_c, c), 8)
    cp = _round_up(c, block)
    dp = _round_up(d, LANES)
    wp = _round_up(adj_bits.shape[1], LANES)
    members = _pad_rows(members.astype(jnp.int32), cp, -1)
    nvalid = _pad_rows(n_valid.astype(jnp.int32), cp, 0)[:, None]
    members_flat, s = _smem_blocks(members.T, block)
    nbr_p = jnp.pad(nbr.astype(jnp.int32), ((0, 0), (0, dp - d)),
                    constant_values=-1)
    plane = pl.BlockSpec((k, block, dp), lambda i: (0, i, 0))
    plane_shape = jax.ShapeDtypeStruct((k, cp, dp), jnp.int32)

    cand, valid, keep = pallas_call(
        _expand_kernel,
        name="expand_canonical",
        grid=(cp // block,),
        in_specs=[
            pl.BlockSpec((s,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((block, k), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((n, dp), lambda i: (0, 0)),  # neighbour table resident
            pl.BlockSpec((n, wp), lambda i: (0, 0)),  # bitmap resident
        ],
        out_specs=[plane, plane, plane],
        out_shape=[plane_shape, plane_shape, plane_shape],
        scratch_shapes=[
            pltpu.VMEM((k, block, dp), jnp.int32),
            pltpu.VMEM((k, block, wp), jnp.int32),
        ],
        vmem_limit_bytes=RESIDENT_VMEM_BYTES,
        interpret=interpret,
    )(members_flat, members, nvalid, nbr_p, _words(adj_bits, wp))

    def back(x):
        return jnp.transpose(x[:, :c, :d], (1, 0, 2))

    return back(cand), back(valid) == 1, back(keep) == 1
