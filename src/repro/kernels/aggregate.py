"""Device-resident level-1 pattern binning (sort + segment-unique/reduce).

The two-level aggregation of paper §5.4 promises that only per-*pattern*
state ever leaves the exploration engine, yet until DESIGN.md §10 the
level-1 fold ran on the host: every superstep drained the full frontier's
(B, 3) quick codes (and the (B, 8) local-vertex table for FSM) to the host
and lexsort-uniqued them there — an O(B) host transfer in the hottest
phase. This module is the device replacement: given a batch of quick codes
it produces, **on device**,

  * ``uniq``   — the distinct codes, lexicographically sorted, padded to a
    static capacity ``cap``;
  * ``counts`` — embeddings per distinct code (optionally weighted, for
    folding pre-binned partial aggregates);
  * ``inv``    — the per-row slot id into ``uniq`` (-1 for invalid rows);
  * ``n``      — the UNCLAMPED distinct total. Like the stream-compaction
    kernel's count contract (``kernels/compact.py``), overflow past ``cap``
    is a pure host decision on an already-drained value: slots ≥ ``cap``
    land in a dump slot that is sliced off, and the caller re-bins at the
    exact pow2 capacity.

The row sort itself stays on ``jax.lax.sort`` — XLA's tuned variadic sort
network. After the sort, segment ids are the inclusive prefix sum of the
segment-boundary flags; the kernel route (``seg_unique_pallas``) computes
that scan with the Pallas prefix-sum kernel of ``kernels/compact.py`` and
leaves the first-occurrence scatter and the per-slot count reduction to
XLA (Mosaic lowers neither ``cumsum`` nor a vector scatter).

Dispatch follows :mod:`repro.kernels.dispatch`: ``interpret=None``
compiles on TPU and interprets elsewhere. The jnp route
(``seg_unique_ref``) honours the identical contract, so the two are
interchangeable inside one jitted program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import compact

#: the int32 count ceiling (DESIGN.md §13): when a pipeline stage must
#: narrow per-pattern counts to int32 (the fused chunk programs' partial
#: emission), it SATURATES at this sentinel instead of wrapping negative.
#: ``DeviceLevel1.fold_partial`` detects the sentinel on device and its
#: finish drain reports it (7th scalar of the one flags read) — the caller
#: then re-folds the step from the frontier waves in int64, so totals past
#: 2^31 stay exact instead of silently corrupting.
I32_SAT = 2**31 - 1


def _seg_tail(newv, valid, cap: int, incl, n):
    """Slots, first-occurrence sources and per-slot counts from the
    inclusive prefix sum ``incl`` of the boundary flags ``newv`` — the
    part of the segment-unique shared by both routes."""
    b = newv.shape[0]
    slot = jnp.where(valid, incl - 1, -1)
    iota = jnp.arange(b, dtype=jnp.int32)
    pos_src = jnp.where(newv & (slot < cap), slot, cap)
    src = jnp.zeros((cap + 1,), jnp.int32).at[pos_src].set(
        jnp.where(newv, iota, 0)
    )[:cap]
    pos_cnt = jnp.where(valid & (slot >= 0) & (slot < cap), slot, cap)
    counts = jax.ops.segment_sum(
        valid.astype(jnp.int32), pos_cnt, num_segments=cap + 1
    )[:cap].astype(jnp.int32)
    return src, counts, slot, n


def _seg_empty(cap: int):
    return (jnp.zeros((cap,), jnp.int32), jnp.zeros((cap,), jnp.int32),
            jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32))


@functools.partial(
    jax.jit, static_argnames=("cap", "block", "interpret")
)
def seg_unique_pallas(new, valid, cap: int, block: int = 32768,
                      interpret=None):
    """(new (B,) bool boundary flags, valid (B,) bool) over SORTED rows ->
    (src (cap,) int32, counts (cap,) int32, slot (B,) int32, n () int32).

    ``src[:min(n, cap)]`` are the first-occurrence indices of each distinct
    segment in ascending order (pad slots 0); ``counts`` the per-segment
    row totals; ``slot`` the per-row segment id (-1 invalid, unclamped past
    ``cap``); ``n`` the unclamped distinct total. Valid rows must form a
    prefix of the sort order (the code sort pushes invalid rows last).
    The boundary-flag prefix sum runs in the Pallas scan
    (``compact.prefix_sum_pallas``); the scatters stay in XLA.
    """
    if new.shape[0] == 0:
        return _seg_empty(cap)
    newv = new & valid
    incl, n = compact.prefix_sum_pallas(newv, block=block,
                                        interpret=interpret)
    return _seg_tail(newv, valid, cap, incl, n)


def seg_unique_ref(new, valid, cap: int):
    """The jnp route (cumsum + scatter + segment_sum) with the kernel's
    exact contract — what ``bin_rows`` uses when the kernel is off."""
    if new.shape[0] == 0:
        return _seg_empty(cap)
    newv = new & valid
    incl = jnp.cumsum(newv.astype(jnp.int32), dtype=jnp.int32)
    return _seg_tail(newv, valid, cap, incl, incl[-1])


def sort_codes(codes, valid):
    """Sort (B, 3) code rows lexicographically with invalid rows pushed
    last. Returns (sorted codes, sorted valid, order).

    Exploits the quick-code encoding (every word < 2^32 by construction:
    4 + 28 structure bits, four 8-bit labels per label word) to pack the
    four sort keys (invalid, w0, w1, w2) into TWO uint64 keys — XLA's
    variadic sort scales with operand count, and the 2-key unstable sort
    is ~2x the 5-operand stable one. Tie order among equal codes is
    irrelevant: every :func:`bin_rows` output is value-determined.
    """
    b = codes.shape[0]
    k1 = (
        jnp.where(valid, 0, 1).astype(jnp.uint64) << 32
    ) | codes[:, 0].astype(jnp.uint64)
    k2 = (
        codes[:, 1].astype(jnp.uint64) << 32
    ) | codes[:, 2].astype(jnp.uint64)
    iota = jnp.arange(b, dtype=jnp.int32)
    _, _, order = jax.lax.sort((k1, k2, iota), num_keys=2, is_stable=False)
    return codes[order], valid[order], order


def bin_rows(codes, valid, cap: int, weights=None, *, use_kernel: bool = False,
             block: int = 32768, interpret=None, method: str = "sort"):
    """Level-1 device binning of one batch of quick codes.

    ``codes`` (B, 3) int64, ``valid`` (B,) bool ->
    ``(uniq (cap, 3) int64, counts (cap,) int64, inv (B,) int32,
    n () int32, uvalid (cap,) bool)``.

    ``uniq`` holds the distinct valid codes in ascending lexicographic
    order (deterministic across every caller — the host path's lexsort
    unique produces the same order, which is what makes the two paths
    bit-identical); ``counts[q]`` sums ``weights`` (default 1) over the
    rows of slot ``q``; ``inv`` maps each input row to its slot (-1
    invalid, *unclamped* on overflow); ``n`` is the unclamped distinct
    total — ``n > cap`` means the dump slot swallowed patterns and the
    caller must re-bin at ``next_pow2(n)``. Plain traced function: call it
    inside a jitted program (the chunk programs, the fold programs) or
    wrap it yourself.

    Precondition (from the quick-code encoding, see :func:`sort_codes`):
    every code word is non-negative and < 2^32.

    ``method`` selects the partition strategy: ``"sort"`` is this
    module's `lax.sort` + segment-unique route; ``"radix"`` routes to
    :mod:`repro.kernels.radix_bin` (Pallas LSB radix / fused-key bucket
    partition) — same contract, bit-identical outputs, chosen per
    backend by the cost model (`runtime/costmodel.py`).
    """
    if method == "radix":
        # late import: radix_bin's slow-path fallback calls back into this
        # module (one-way lazy edge breaks the cycle). The module holds no
        # jnp-valued globals, so importing mid-trace is safe.
        from repro.kernels import radix_bin

        return radix_bin.bin_rows_radix(
            codes, valid, cap, weights,
            use_kernel=use_kernel, block=block, interpret=interpret,
        )
    b = codes.shape[0]
    if b == 0:
        return (jnp.zeros((cap, 3), jnp.int64), jnp.zeros((cap,), jnp.int64),
                jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32),
                jnp.zeros((cap,), bool))
    if weights is None and b >= I32_SAT:
        # static wide guard: the unweighted path accumulates per-slot
        # counts in int32 inside the seg-unique kernels, exact only while
        # a slot's count (<= B, a static shape) fits — past that, route
        # through the int64 weighted segment-sum instead of wrapping
        weights = jnp.ones((b,), jnp.int64)
    sc, sv, order = sort_codes(codes, valid)
    prev_diff = jnp.concatenate(
        [jnp.ones((1,), bool), (sc[1:] != sc[:-1]).any(axis=1)]
    )
    new = sv & prev_diff
    if use_kernel:
        src, counts32, slot, n = seg_unique_pallas(
            new, sv, cap, block=block, interpret=interpret
        )
    else:
        src, counts32, slot, n = seg_unique_ref(new, sv, cap)
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


def pack_codes_u32(uniq):
    """Lossless device-side packing of (Q, 3) int64 quick codes to uint32.

    By construction (``repro.core.pattern``): ``w0 = nv | bits << 4`` with
    ``nv <= 8`` and at most C(8,2) = 28 adjacency bits (32 bits total);
    ``w1``/``w2`` hold four 8-bit labels each. All three words fit uint32
    exactly, halving the aggregation bytes that cross to the host."""
    return uniq.astype(jnp.uint32)


def unpack_codes_u32(packed) -> "np.ndarray":  # noqa: F821 - host side
    """Host-side inverse of :func:`pack_codes_u32` (numpy)."""
    import numpy as np

    return np.asarray(packed, dtype=np.uint32).astype(np.int64)
