"""Radix/bucket-partition level-1 binning (`bin_rows` without `lax.sort`).

BENCH_8 was blunt about the device aggregation bin on CPU: `lax.sort`
over the packed 2x`uint64` quick-pattern keys costs ~140 ms of the
~200 ms `bin_rows` spends on a 350k-row batch, and the whole device
path loses wall time to the host `aggregate_rows` there. The culprit is
specific: XLA's *variadic* sort (keys + payload operands) runs a
comparator network ~5x slower than its single-operand sort on CPU
(measured 141 ms vs 26 ms at 350k rows). This module removes the
payload-carrying sort from the bin in both directions:

* ``radix_sort_codes`` — a multi-pass LSB radix sort in Pallas: one
  8-bit digit per pass over the quick-code words (w2, w1, w0, then the
  invalid flag, least-significant first), each pass a block-histogram
  kernel + host-free exclusive scan + a stable rank kernel whose
  per-digit write cursor is carried across the sequential grid in a
  revisited output tile — the same grid-carried-total dataflow as
  ``kernels/compact.py`` — and an XLA scatter that applies the
  permutation. Passes whose digit is constant over the batch
  (unlabeled graphs zero both label words) are skipped with `lax.cond`,
  so the common workloads pay for the bits they actually use.

* ``bin_rows_radix`` (jnp route) — a *bucket-partition* fallback built
  on the fast single-operand sort: the three code words are fused into
  ONE `uint64` key at their measured bit-widths (a runtime reduction;
  quick codes use 4 + 28 structure bits plus 8 bits per label, so
  labeled size-3/4 patterns fit comfortably), sorted payload-free, and
  the permutation is never materialised — per-row slots come back from
  a binary-search gather against the sorted keys and counts from
  segment-boundary differences. When the words genuinely need more
  than 63 bits, a `lax.cond` falls back to the 2-key sort path inside
  the same jitted program, so the contract is exact for every input.

Both routes honour ``aggregate.bin_rows``'s exact contract — distinct
codes ascending-lex, unclamped ``n``, per-row ``inv`` unclamped past
``cap`` (-1 invalid), dump-slot overflow sliced off — which is what lets
the cost model (`runtime/costmodel.py`) flip `aggregate_bin` between
"sort" and "radix" without changing a single emitted count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import pallas_call

#: digit width of one radix pass; 256-entry histograms stay VMEM-trivial.
RADIX_BITS = 8
#: real digit channels per pass ...
NDIGITS = 1 << RADIX_BITS
#: ... plus one reserved channel that only block-padding rows occupy, so
#: pads sort stably after every real row in every pass and never
#: interleave with genuine high-digit codes.
PAD_DIGIT = NDIGITS

#: digit channels padded to whole 128-lane vregs (257 -> 384).
CHANNELS = 3 * 128
LANES = 128
#: sort rows per grid step: one (8, 128) lane-dense tile of digits.
SORT_BLOCK = 8 * LANES

#: (word index, shift) per pass, least-significant digit first; word
#: index 3 is the synthesized invalid flag that pushes invalid rows last.
_PASSES = (
    (2, 0), (2, 8), (2, 16), (2, 24),
    (1, 0), (1, 8), (1, 16), (1, 24),
    (0, 0), (0, 8), (0, 16), (0, 24),
    (3, 0),
)

#: kept a Python int (not a jnp constant): module import may happen inside
#: an active jit trace (lazy ``method="radix"`` dispatch), where a
#: module-level jnp op would capture a tracer and leak it across traces.
_SENTINEL = 0xFFFFFFFFFFFFFFFF


def _digit_hits(digits_ref):
    """The block's digits as one-hot rows, 128 sort rows at a time.

    The (S, 128) lane-dense tile is transposed once, so column ``r`` holds
    sort rows ``r*128 .. r*128+127`` in order; yields ``(r, hit)`` with
    ``hit`` the (128, CHANNELS) one-hot matrix of those rows."""
    d_t = digits_ref[...].T                                   # (128, S)
    chan = jax.lax.broadcasted_iota(jnp.int32, (LANES, CHANNELS), 1)
    for r in range(d_t.shape[1]):
        yield r, d_t[:, r:r + 1] == chan


def _hist_kernel(digits_ref, hist_ref):
    """Digit histogram: every grid step adds its block's per-digit counts
    to the revisited (8, CHANNELS) accumulator (all 8 rows equal)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros(hist_ref.shape, jnp.int32)

    acc = jnp.zeros((1, CHANNELS), jnp.int32)
    for _, hit in _digit_hits(digits_ref):
        acc = acc + hit.astype(jnp.int32).sum(axis=0, keepdims=True)
    hist_ref[...] += jnp.broadcast_to(acc, hist_ref.shape)


def _rank_kernel(digits_ref, bases_ref, pos_ref, cursor_ref):
    """One stable counting-sort block: the destination of every row is
    the carried cursor of its digit plus its rank among same-digit rows
    before it. The rank is an inclusive prefix over rows of the one-hot
    digit matrix, one MXU matmul per 128 rows against a lower-triangular
    ones block (exact: counts <= 128); ``cursor_ref`` is the revisited
    grid-carried total, seeded from the global exclusive scan on the
    first step (compact.py idiom)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        cursor_ref[...] = bases_ref[...]

    rr = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    rc = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    lower = (rc <= rr).astype(jnp.bfloat16)
    cursor = cursor_ref[0:1, :]                               # (1, CH)
    s = pos_ref.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (LANES, s), 1)
    pos_t = jnp.zeros((LANES, s), jnp.int32)
    for r, hit in _digit_hits(digits_ref):
        incl = jnp.dot(lower, hit.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)    # (128, CH)
        rank = jnp.sum(jnp.where(hit, incl, 0.0), axis=1, keepdims=True)
        base = jnp.sum(jnp.where(hit, cursor, 0), axis=1, keepdims=True)
        pos_t = jnp.where(col == r, base + rank.astype(jnp.int32) - 1,
                          pos_t)
        cursor = cursor + incl[LANES - 1:LANES, :].astype(jnp.int32)
    pos_ref[...] = pos_t.T
    cursor_ref[...] = jnp.broadcast_to(cursor, cursor_ref.shape)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def radix_sort_codes(codes, valid, block: int = SORT_BLOCK, interpret=None):
    """Stable LSB-radix sort of (B, 3) quick-code rows, invalid rows last.

    Same contract as ``aggregate.sort_codes``: returns (sorted codes,
    sorted valid, order). Each 8-bit pass runs only if its digit varies
    over the batch (`lax.cond`), so e.g. unlabeled motifs pay for the
    structure word alone; the payload permuted through the passes is the
    row index only. Per pass, two Pallas kernels compute the digit
    histogram and every row's destination over lane-dense (8k, 128)
    digit tiles; XLA applies the permutation (Mosaic has no vector
    scatter). ``block`` (sort rows per grid step) is rounded up to a
    multiple of :data:`SORT_BLOCK`.
    """
    b = codes.shape[0]
    block = -(-max(1, int(block)) // SORT_BLOCK) * SORT_BLOCK
    pad = (-b) % block
    nblocks = (b + pad) // block
    rows = block // LANES

    # word bit-patterns as int32 (quick-code words are < 2^32 by
    # construction); byte-wise digits of the two's-complement pattern
    # order exactly as the unsigned words do
    words = jax.lax.bitcast_convert_type(
        codes.astype(jnp.uint32), jnp.int32
    )
    invalid = jnp.where(valid, 0, 1).astype(jnp.int32)
    order = jnp.arange(b, dtype=jnp.int32)
    pad_digits = jnp.full((pad,), PAD_DIGIT, jnp.int32)
    pad_payload = jnp.zeros((pad,), jnp.int32)
    digit_spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    tile_spec = pl.BlockSpec((8, CHANNELS), lambda i: (0, 0))
    tile_shape = jax.ShapeDtypeStruct((8, CHANNELS), jnp.int32)

    def one_pass(order, digits):
        dp = jnp.concatenate([digits, pad_digits]).reshape(-1, LANES)
        op = jnp.concatenate([order, pad_payload])
        hist = pallas_call(
            _hist_kernel,
            name="radix_hist",
            grid=(nblocks,),
            in_specs=[digit_spec],
            out_specs=tile_spec,
            out_shape=tile_shape,
            interpret=interpret,
        )(dp)
        totals = hist[0]
        bases = jnp.cumsum(totals, dtype=jnp.int32) - totals
        pos, _ = pallas_call(
            _rank_kernel,
            name="radix_rank",
            grid=(nblocks,),
            in_specs=[digit_spec, tile_spec],
            out_specs=[digit_spec, tile_spec],
            out_shape=[
                jax.ShapeDtypeStruct(dp.shape, jnp.int32),
                tile_shape,
            ],
            interpret=interpret,
        )(dp, jnp.broadcast_to(bases, (8, CHANNELS)))
        out = jnp.zeros((b + pad,), jnp.int32).at[pos.reshape(-1)].set(op)
        return out[:b]

    for word, shift in _PASSES:
        if word == 3:
            src = invalid
        else:
            src = words[:, word]
        digits = jax.lax.shift_right_logical(
            src[order], jnp.int32(shift)
        ) & jnp.int32(0xFF)
        # a constant digit permutes nothing — skip the pass at runtime
        varies = jnp.min(digits) != jnp.max(digits)
        order = jax.lax.cond(
            varies, lambda o, d: one_pass(o, d), lambda o, d: o,
            order, digits,
        )
    return codes[order], valid[order], order


def _fused_keys(codes, valid):
    """Reduce (B, 3) code words to ONE uint64 sort key at their measured
    bit-widths. Returns (key, widths (b1, b2), fits) — ``fits`` is the
    runtime flag that all three words share 63 bits (the top bit is kept
    clear so valid keys stay below the invalid sentinel)."""
    z = jnp.uint64(0)

    def width(w):
        m = jnp.max(jnp.where(valid, w, z))
        return jnp.where(
            m > 0, jnp.uint64(64) - jax.lax.clz(m).astype(jnp.uint64),
            jnp.uint64(0),
        )

    c0 = codes[:, 0].astype(jnp.uint64)
    c1 = codes[:, 1].astype(jnp.uint64)
    c2 = codes[:, 2].astype(jnp.uint64)
    b0, b1, b2 = width(c0), width(c1), width(c2)
    fits = (b0 + b1 + b2) <= jnp.uint64(63)
    key = jnp.where(
        valid, (((c0 << b1) | c1) << b2) | c2, jnp.uint64(_SENTINEL)
    )
    return key, (b1, b2), fits


def _bin_fused(codes, valid, cap, weights, key, widths):
    """Bucket-partition bin over the fused single-word key: one
    payload-free sort, then slots/counts recovered by gathers alone."""
    b = codes.shape[0]
    b1, b2 = widths
    skey = jax.lax.sort((key,), num_keys=1)[0]
    boundary = jnp.concatenate(
        [jnp.ones((1,), bool), skey[1:] != skey[:-1]]
    )
    svalid = skey != jnp.uint64(_SENTINEL)
    newv = boundary & svalid
    n = newv.sum(dtype=jnp.int32)
    # dense rank of every sorted position's distinct key (unclamped)
    rank = jnp.cumsum(newv.astype(jnp.int32), dtype=jnp.int32) - 1
    # first-occurrence positions of the first `cap` distinct keys
    (bpos,) = jnp.nonzero(newv, size=cap + 1, fill_value=b)
    total_valid = svalid.sum(dtype=jnp.int64)
    nxt = jnp.concatenate([bpos[1:], jnp.full((1,), b)])
    seg_end = jnp.minimum(nxt, total_valid)
    seg_start = jnp.minimum(bpos, total_valid)
    uvalid = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(n, cap)
    # per-row slot: binary search for the row's key among the sorted
    # keys, then the dense rank at that (first-occurrence) position —
    # exact and unclamped even past cap, with zero scatters
    first = jnp.searchsorted(skey, key).astype(jnp.int32)
    inv = jnp.where(valid, rank[jnp.minimum(first, b - 1)], -1)
    # distinct keys unpacked back to the three words
    dkey = jnp.where(uvalid, skey[jnp.minimum(bpos[:cap], b - 1)], 0)
    one = jnp.uint64(1)
    u2 = dkey & ((one << b2) - one)
    u1 = (dkey >> b2) & ((one << b1) - one)
    u0 = dkey >> (b1 + b2)
    uniq = jnp.stack(
        [u0.astype(jnp.int64), u1.astype(jnp.int64), u2.astype(jnp.int64)],
        axis=1,
    )
    uniq = jnp.where(uvalid[:, None], uniq, 0)
    if weights is None:
        counts = jnp.maximum(seg_end - seg_start, 0)[:cap] * uvalid
    else:
        seg = jnp.where(valid & (inv >= 0) & (inv < cap), inv, cap)
        counts = jax.ops.segment_sum(
            jnp.where(valid, weights, 0).astype(jnp.int64), seg,
            num_segments=cap + 1,
        )[:cap]
    return uniq, counts.astype(jnp.int64), inv, n, uvalid


def bin_rows_radix(codes, valid, cap: int, weights=None, *,
                   use_kernel: bool = False, block: int = 32768,
                   interpret=None):
    """Level-1 binning with the radix/bucket partition in place of the
    payload-carrying ``lax.sort`` — the exact `aggregate.bin_rows`
    contract (see that docstring for the output shapes and the unclamped
    overflow semantics).

    ``use_kernel=True`` routes the sort through the Pallas LSB-radix
    passes; otherwise the fused
    single-key jnp route runs, with a traced `lax.cond` fallback to the
    2-key sort bin for batches whose words exceed 63 used bits.
    """
    from repro.kernels import aggregate as _agg

    b = codes.shape[0]
    if b == 0:
        return (jnp.zeros((cap, 3), jnp.int64), jnp.zeros((cap,), jnp.int64),
                jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32),
                jnp.zeros((cap,), bool))
    if weights is None and b >= _agg.I32_SAT:
        weights = jnp.ones((b,), jnp.int64)

    if use_kernel:
        sc, sv, order = radix_sort_codes(codes, valid, interpret=interpret)
        prev_diff = jnp.concatenate(
            [jnp.ones((1,), bool), (sc[1:] != sc[:-1]).any(axis=1)]
        )
        new = sv & prev_diff
        src, counts32, slot, n = _agg.seg_unique_pallas(
            new, sv, cap, block=block, interpret=interpret
        )
        uvalid = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(n, cap)
        uniq = jnp.where(uvalid[:, None], sc[jnp.minimum(src, b - 1)], 0)
        if weights is None:
            counts = counts32.astype(jnp.int64)
        else:
            w_sorted = jnp.where(sv, weights[order], 0).astype(jnp.int64)
            seg = jnp.where(sv & (slot >= 0) & (slot < cap), slot, cap)
            counts = jax.ops.segment_sum(
                w_sorted, seg, num_segments=cap + 1
            )[:cap]
        inv = jnp.zeros((b,), jnp.int32).at[order].set(slot)
        return uniq, counts, inv, n, uvalid

    key, widths, fits = _fused_keys(codes, valid)
    w_arg = (jnp.zeros((b,), jnp.int64) if weights is None
             else weights.astype(jnp.int64))

    def fast(codes, valid, w):
        return _bin_fused(
            codes, valid, cap, None if weights is None else w, key, widths
        )

    def slow(codes, valid, w):
        return _agg.bin_rows(
            codes, valid, cap, None if weights is None else w,
            use_kernel=False, block=block, interpret=interpret,
        )

    return jax.lax.cond(fits, fast, slow, codes, valid, w_arg)
