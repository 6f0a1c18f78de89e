"""Batched device canonical refinement — level 2 on device (DESIGN.md §15).

Level 2 of the paper's two-level aggregation canonicalises each *distinct*
quick pattern (§5.4). The host implementation (`core/canon_math.py`) brute
forces the k! vertex-position permutations in numpy; on labeled graphs the
distinct-pattern table alone reaches tens of thousands of rows (mico: 37k
size-3 quick patterns) and that host pass becomes the last O(work) host
phase of the superstep. This module is the device replacement: a batched
permutation-refinement kernel over the O(Q) unique-code table that emits

  * ``canon``  — the lexicographically minimal (w0, w1, w2) encoding over
    all permutations, per row;
  * ``sigma``  — local→canonical position map of the FIRST minimal
    permutation (``itertools.permutations`` order), identity for pos ≥ nv;
  * ``rep``    — automorphism-orbit representative per position
    (min over the automorphism group — run it on *canonical* codes).

all bit-identical to :func:`canon_math.canonicalize_one` /
:func:`canon_math.automorphism_orbits`.

Dataflow: permutations act on the *encoded* words directly — a host-built
per-nv table (``canon_math.perm_tables``) maps each target adjacency bit
to its source bit under every permutation, so a permuted w0 is 28 shift/or
ops per permutation tile and never touches a dense (nv, nv) matrix. Code
words are 32-bit patterns (every code word < 2^32 by construction) and
the permutation axis is tiled with a running cross-tile argmin whose
strict-less merge preserves the first-minimal-wins tie-break.

Routes: ``_refine_nv_jnp`` (lax.fori_loop over permutation tiles) and
``_refine_nv_pallas`` (grid = rows × permutation tiles, revisited output
windows carrying the running best — the compact.py idiom; int32 words
compared through an order-preserving bias, since Mosaic reduces no
unsigned integers). Same contract,
interchangeable inside one jitted program; dispatch follows
:mod:`repro.kernels.dispatch`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import canon_math
from repro.kernels.dispatch import pallas_call

#: permutation-axis tile (the fori/grid step); 128 divides 8! and bounds
#: the (rows × tile) key intermediates to VMEM-friendly sizes.
PERM_TILE = 128
#: row-axis block of the Pallas route.
ROW_BLOCK = 128
#: adjacency bits of an 8-vertex pattern — the padded bit-source width.
MAX_BITS = canon_math.n_pair_bits(canon_math.MAX_PATTERN_VERTICES)

_U32_MAX = np.uint32(0xFFFFFFFF)


def _padded_tables(nv: int, tile: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-nv permutation tables padded for tiled device iteration.

    ``perms`` (P', 8) int32: columns ≥ nv hold the identity position (their
    gathered label is 0, so padded columns contribute nothing to w1/w2 and
    the one-scatter sigma recovery yields identity there). ``src`` (P', 28)
    int32: target bits ≥ n_pair_bits(nv) read source bit 31, which is 0 in
    every code word (bits occupy ≤ 28 + 4 positions... bit 31 is never set
    for nv ≤ 8 since adj_bits < 2^28). Rows are padded to a multiple of
    ``tile`` by REPEATING the last permutation: duplicates can never win
    the strict-less merge and the orbit min is idempotent.
    """
    perms, src = canon_math.perm_tables(nv)
    p = len(perms)
    nbits = canon_math.n_pair_bits(nv)
    perms_pad = np.tile(np.arange(8, dtype=np.int32), (p, 1))
    perms_pad[:, :nv] = perms
    src_pad = np.full((p, MAX_BITS), 31, dtype=np.int32)
    src_pad[:, :nbits] = src
    rows = -(-p // tile) * tile
    if rows > p:
        perms_pad = np.concatenate(
            [perms_pad, np.tile(perms_pad[-1:], (rows - p, 1))]
        )
        src_pad = np.concatenate(
            [src_pad, np.tile(src_pad[-1:], (rows - p, 1))]
        )
    return perms_pad, src_pad


def _split_codes(codes):
    """(Q, 3) int64 codes -> (bits (Q,) uint32, labels (Q, 8) uint32,
    own (Q, 3) uint32). Exact: every code word < 2^32."""
    cu = codes.astype(jnp.uint32)
    bits = cu[:, 0] >> 4
    lab_cols = []
    for i in range(4):
        lab_cols.append((cu[:, 1] >> (8 * i)) & jnp.uint32(0xFF))
    for i in range(4):
        lab_cols.append((cu[:, 2] >> (8 * i)) & jnp.uint32(0xFF))
    labels = jnp.stack(lab_cols, axis=1)
    return bits, labels, cu


def _permuted_keys(bits, labels, pt, st, nv: int):
    """Keys of every (row, permutation-in-tile) pair.

    ``bits`` (R,) uint32, ``labels`` (R, 8) uint32, ``pt`` (T, 8) int32
    padded perms, ``st`` (T, 28) int32 padded bit sources ->
    (w0, w1, w2) each (R, T) uint32. Label gather is 8×8 selects (no
    dynamic gather — lowers on every backend, Pallas included)."""
    nbits = canon_math.n_pair_bits(nv)
    new_bits = jnp.zeros((bits.shape[0], pt.shape[0]), jnp.uint32)
    for tb in range(nbits):
        s = st[:, tb].astype(jnp.uint32)
        new_bits = new_bits | (
            ((bits[:, None] >> s[None, :]) & jnp.uint32(1)) << tb
        )
    w0 = (new_bits << 4) | jnp.uint32(nv)
    w1 = jnp.zeros_like(new_bits)
    w2 = jnp.zeros_like(new_bits)
    for i in range(8):
        pti = pt[:, i][None, :]                              # (1, T)
        li = jnp.zeros_like(new_bits)
        for s in range(8):
            li = li | jnp.where(pti == s, labels[:, s][:, None],
                                jnp.uint32(0))
        if i < 4:
            w1 = w1 | (li << (8 * i))
        else:
            w2 = w2 | (li << (8 * (i - 4)))
    return w0, w1, w2


def _tile_first_min(w0, w1, w2):
    """Per-row lexicographic minimum over the tile axis + the FIRST column
    achieving it (three-stage masked min, then argmax of eligibility —
    jnp.argmax returns the first maximal index)."""
    m0 = w0.min(axis=1, keepdims=True)
    e = w0 == m0
    m1 = jnp.where(e, w1, _U32_MAX).min(axis=1, keepdims=True)
    e = e & (w1 == m1)
    m2 = jnp.where(e, w2, _U32_MAX).min(axis=1, keepdims=True)
    e = e & (w2 == m2)
    loc = jnp.argmax(e, axis=1).astype(jnp.int32)
    return m0[:, 0], m1[:, 0], m2[:, 0], loc


def _lex_less3(a0, a1, a2, b0, b1, b2):
    return (a0 < b0) | (
        (a0 == b0) & ((a1 < b1) | ((a1 == b1) & (a2 < b2)))
    )


def _identity_rows(q):
    return jnp.tile(jnp.arange(8, dtype=jnp.int32), (q, 1))


def _sigma_from_pi(best_pi, perms_dev):
    """sigma[local] = canonical position, via one scatter of the winning
    permutation (padded columns are identity, so pos ≥ nv comes out
    identity exactly as the host contract requires)."""
    chosen = perms_dev[best_pi]                               # (Q, 8) int32
    q = chosen.shape[0]
    rows = jnp.arange(q, dtype=jnp.int32)[:, None]
    return jnp.zeros((q, 8), jnp.int32).at[rows, chosen].set(
        jnp.arange(8, dtype=jnp.int32)[None, :]
    )


# ---------------------------------------------------------------------------
# jnp reference route
# ---------------------------------------------------------------------------

def _refine_nv_jnp(codes, nv: int, with_orbits: bool, tile: int):
    """Single-nv refine, lax.fori_loop over permutation tiles. Returns
    (canon (Q, 3) int64, sigma (Q, 8) int32, rep (Q, 8) int32); rows whose
    actual nv differs produce garbage the caller masks out."""
    q = codes.shape[0]
    perms_np, src_np = _padded_tables(nv, tile)
    perms_dev = jnp.asarray(perms_np)
    src_dev = jnp.asarray(src_np)
    bits, labels, own = _split_codes(codes)
    n_tiles = len(perms_np) // tile

    def body(j, carry):
        b0, b1, b2, bpi, rep = carry
        pt = jax.lax.dynamic_slice(perms_dev, (j * tile, 0), (tile, 8))
        st = jax.lax.dynamic_slice(src_dev, (j * tile, 0), (tile, MAX_BITS))
        w0, w1, w2 = _permuted_keys(bits, labels, pt, st, nv)
        m0, m1, m2, loc = _tile_first_min(w0, w1, w2)
        tpi = j.astype(jnp.int32) * tile + loc
        better = _lex_less3(m0, m1, m2, b0, b1, b2)
        b0 = jnp.where(better, m0, b0)
        b1 = jnp.where(better, m1, b1)
        b2 = jnp.where(better, m2, b2)
        bpi = jnp.where(better, tpi, bpi)
        if with_orbits:
            auto = (
                (w0 == own[:, 0:1]) & (w1 == own[:, 1:2])
                & (w2 == own[:, 2:3])
            )
            cand = jnp.where(auto[:, :, None], pt[None, :, :],
                             jnp.int32(8)).min(axis=1)
            rep = jnp.minimum(rep, cand)
        return b0, b1, b2, bpi, rep

    init = (
        jnp.full((q,), _U32_MAX, jnp.uint32),
        jnp.full((q,), _U32_MAX, jnp.uint32),
        jnp.full((q,), _U32_MAX, jnp.uint32),
        jnp.zeros((q,), jnp.int32),
        _identity_rows(q),
    )
    b0, b1, b2, bpi, rep = jax.lax.fori_loop(0, n_tiles, body, init)
    canon = jnp.stack([b0, b1, b2], axis=1).astype(jnp.int64)
    sigma = _sigma_from_pi(bpi, perms_dev)
    return canon, sigma, rep


# ---------------------------------------------------------------------------
# Pallas route
# ---------------------------------------------------------------------------

#: signed-order bias: ``x ^ _BIAS`` maps uint32 order onto int32 order, so
#: the kernel compares and reduces signed words (Mosaic reduces no
#: unsigned integers).
_BIAS = -(2**31)
_I32_MAX = 2**31 - 1


def _refine_kernel(codes_ref, labels_ref, perms_ref, src_ref,
                   best_ref, rep_ref, *, nv: int, tile: int,
                   with_orbits: bool):
    """Grid step (i, j) = (row block, permutation tile): permute keys for
    the tile, fold its first-min into the revisited best/rep windows.
    The permutation axis is the FAST grid dimension, so for a fixed row
    block j sweeps all tiles before i advances — the running windows carry
    across j and re-initialise at j == 0.

    Rows lie on sublanes and permutations on lanes: ``perms_ref`` /
    ``src_ref`` arrive transposed ((8, T) / (28, T)), so every key plane
    is an (R, T) int32 tile. Words are uint32 bit patterns held in int32;
    ``best_ref`` columns 0-2 hold the running minimum in biased (signed)
    form and column 3 its permutation index."""
    j = pl.program_id(1)
    rows = best_ref.shape[0]
    col8 = jax.lax.broadcasted_iota(jnp.int32, (rows, 8), 1)

    @pl.when(j == 0)
    def _init():
        best_ref[...] = jnp.where(col8 < 3, jnp.int32(_I32_MAX), 0)
        rep_ref[...] = col8

    codes = codes_ref[...]                                    # (R, 3)
    bits = codes[:, 0:1] >> 4                                 # < 2^28
    labels = labels_ref[...]                                  # (R, 8)
    pt = perms_ref[...]                                       # (8, T)
    st = src_ref[...]                                         # (28, T)
    new_bits = jnp.zeros((rows, tile), jnp.int32)
    for tb in range(canon_math.n_pair_bits(nv)):
        new_bits = new_bits | (((bits >> st[tb:tb + 1, :]) & 1) << tb)
    w0 = (new_bits << 4) | nv
    w1 = jnp.zeros_like(new_bits)
    w2 = jnp.zeros_like(new_bits)
    for i in range(8):
        pti = pt[i:i + 1, :]                                  # (1, T)
        li = jnp.zeros_like(new_bits)
        for src in range(8):
            li = jnp.where(pti == src, labels[:, src:src + 1], li)
        if i < 4:
            w1 = w1 | (li << (8 * i))
        else:
            w2 = w2 | (li << (8 * (i - 4)))
    b0, b1, b2 = w0 ^ _BIAS, w1 ^ _BIAS, w2 ^ _BIAS
    # first lexicographic minimum over the tile (three-stage masked min,
    # then the lowest column achieving it)
    m0 = b0.min(axis=1, keepdims=True)
    e = b0 == m0
    m1 = jnp.where(e, b1, _I32_MAX).min(axis=1, keepdims=True)
    e = e & (b1 == m1)
    m2 = jnp.where(e, b2, _I32_MAX).min(axis=1, keepdims=True)
    e = e & (b2 == m2)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 1)
    loc = jnp.where(e, col, tile).min(axis=1, keepdims=True)
    best = best_ref[...]
    c0, c1, c2 = best[:, 0:1], best[:, 1:2], best[:, 2:3]
    better = _lex_less3(m0, m1, m2, c0, c1, c2)
    upd = jnp.where(col8 == 0, m0, jnp.where(col8 == 1, m1, jnp.where(
        col8 == 2, m2, j * tile + loc)))
    best_ref[...] = jnp.where(better & (col8 < 4), upd, best)
    if with_orbits:
        auto = ((w0 == codes[:, 0:1]) & (w1 == codes[:, 1:2])
                & (w2 == codes[:, 2:3]))
        rep = rep_ref[...]
        for pos in range(8):
            cand = jnp.where(auto, pt[pos:pos + 1, :], 8).min(
                axis=1, keepdims=True
            )
            rep = jnp.where(col8 == pos, jnp.minimum(rep, cand), rep)
        rep_ref[...] = rep


def _refine_nv_pallas(codes, nv: int, with_orbits: bool, tile: int,
                      row_block: int, interpret):
    """Single-nv refine through the Pallas kernel (same contract as
    :func:`_refine_nv_jnp`)."""
    q = codes.shape[0]
    perms_np, src_np = _padded_tables(nv, tile)
    perms_dev = jnp.asarray(perms_np)
    _, labels, cu = _split_codes(codes)
    words = jax.lax.bitcast_convert_type(cu, jnp.int32)
    labels = labels.astype(jnp.int32)
    row_block = -(-max(1, min(row_block, q)) // 8) * 8
    pad = (-q) % row_block
    if pad:
        words = jnp.concatenate([words, jnp.zeros((pad, 3), jnp.int32)])
        labels = jnp.concatenate([labels, jnp.zeros((pad, 8), jnp.int32)])
    n_tiles = len(perms_np) // tile
    best, rep = pallas_call(
        functools.partial(_refine_kernel, nv=nv, tile=tile,
                          with_orbits=with_orbits),
        name="canonical_refine",
        grid=((q + pad) // row_block, n_tiles),
        in_specs=[
            pl.BlockSpec((row_block, 3), lambda i, j: (i, 0)),
            pl.BlockSpec((row_block, 8), lambda i, j: (i, 0)),
            pl.BlockSpec((8, tile), lambda i, j: (0, j)),
            pl.BlockSpec((MAX_BITS, tile), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((row_block, 8), lambda i, j: (i, 0)),
            pl.BlockSpec((row_block, 8), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q + pad, 8), jnp.int32),
            jax.ShapeDtypeStruct((q + pad, 8), jnp.int32),
        ],
        interpret=interpret,
    )(words, labels, jnp.asarray(perms_np.T), jnp.asarray(src_np.T))
    canon = jax.lax.bitcast_convert_type(
        best[:q, :3] ^ jnp.int32(_BIAS), jnp.uint32
    ).astype(jnp.int64)
    sigma = _sigma_from_pi(best[:q, 3], perms_dev)
    return canon, sigma, rep[:q]


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def refine_codes(codes, valid, nvs: tuple, *, with_orbits: bool = False,
                 use_kernel: bool = False, interpret=None,
                 tile: int = PERM_TILE, row_block: int = ROW_BLOCK):
    """Mixed-nv batched canonical refine (plain traced function — call it
    inside a jitted program, or use :func:`refine_batch`).

    ``codes`` (Q, 3) int64, ``valid`` (Q,) bool, ``nvs`` the STATIC tuple
    of vertex counts that may occur ->
    ``(canon (Q, 3) int64, sigma (Q, 8) int32, rep (Q, 8) int32)``.

    One refine pass per nv in ``nvs``; each row takes the pass matching its
    encoded nv. Rows with nv ≤ 1, rows whose nv is outside ``nvs``, and
    invalid rows pass through unchanged with identity sigma/rep (exactly
    the host contract for nv ≤ 1). ``rep`` is the orbit table of the INPUT
    codes — meaningful on canonical codes (Aut(canon) ≠ Aut(quick))."""
    q = codes.shape[0]
    canon = codes.astype(jnp.int64)
    sigma = _identity_rows(q)
    rep = _identity_rows(q)
    if q == 0:
        return canon, sigma, rep
    row_nv = (codes[:, 0] & 0xF).astype(jnp.int32)
    for nv in sorted(set(int(v) for v in nvs)):
        if nv <= 1 or nv > canon_math.MAX_PATTERN_VERTICES:
            continue
        if use_kernel:
            c, s, r = _refine_nv_pallas(codes, nv, with_orbits, tile,
                                        row_block, interpret)
        else:
            c, s, r = _refine_nv_jnp(codes, nv, with_orbits, tile)
        m = valid & (row_nv == nv)
        canon = jnp.where(m[:, None], c, canon)
        sigma = jnp.where(m[:, None], s, sigma)
        rep = jnp.where(m[:, None], r, rep)
    return canon, sigma, rep


@functools.partial(
    jax.jit,
    static_argnames=("nvs", "with_orbits", "use_kernel", "interpret",
                     "tile", "row_block"),
)
def refine_batch(codes, valid, nvs: tuple, with_orbits: bool = False,
                 use_kernel: bool = False, interpret=None,
                 tile: int = PERM_TILE, row_block: int = ROW_BLOCK):
    """Jitted :func:`refine_codes` (standalone use: tests, host helper,
    cost-model probe)."""
    return refine_codes(codes, valid, nvs, with_orbits=with_orbits,
                        use_kernel=use_kernel, interpret=interpret,
                        tile=tile, row_block=row_block)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def canonicalize_on_device(codes_np, *, with_orbits: bool = False,
                           use_kernel: bool = False, interpret=None):
    """Host convenience: numpy (M, 3) int64 mixed-nv codes -> numpy
    ``(canon (M, 3) int64, sigma (M, 8) int32, rep (M, 8) int32)`` via the
    device kernel. Pads the batch to the next power of two so repeated
    calls reuse a bounded set of compiled shapes. This is the
    ``canon_fn`` hook of :func:`pattern.build_pattern_table` and the
    cost-model probe body."""
    codes_np = np.ascontiguousarray(codes_np, dtype=np.int64)
    m = len(codes_np)
    if m == 0:
        return (codes_np.copy(),
                np.zeros((0, 8), np.int32), np.zeros((0, 8), np.int32))
    nvs = tuple(sorted(set(int(w) & 0xF for w in codes_np[:, 0])))
    cap = _next_pow2(m)
    padded = np.zeros((cap, 3), dtype=np.int64)
    padded[:m] = codes_np
    valid = np.zeros((cap,), dtype=bool)
    valid[:m] = True
    canon, sigma, rep = refine_batch(
        jnp.asarray(padded), jnp.asarray(valid), nvs,
        with_orbits=with_orbits, use_kernel=use_kernel, interpret=interpret,
    )
    return (np.asarray(canon[:m]), np.asarray(sigma[:m]),
            np.asarray(rep[:m]))


def make_canon_fn(*, use_kernel: bool = False, interpret=None):
    """A :func:`pattern.build_pattern_table` ``canon_fn`` bound to the
    device refine (placement "device" over a host-resident level-1)."""
    def canon_fn(miss_codes):
        canon, sigma, _ = canonicalize_on_device(
            miss_codes, use_kernel=use_kernel, interpret=interpret
        )
        return canon, sigma
    return canon_fn
