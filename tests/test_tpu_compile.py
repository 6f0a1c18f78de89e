"""Ahead-of-time compiles of every main-path Pallas kernel for TPU v5e.

Each test lowers one kernel entry point at the widths ``chip_smoke.py``
drives on the chip (the CiteSeer-shaped graph: 3,312 vertices, maximum
degree 252, a 4,096-row chunk) against a *described* v5e topology and
compiles it with the TPU compiler installed here — no chip is attached.
The compile refuses what interpret-mode tests cannot see: blocks that
break the (8, 128) tiling, primitives Mosaic cannot lower, 64-bit values
in a kernel body, more VMEM than a kernel may use.

The topology is described inside a module fixture (never at import), and
the fixture skips where no v5e topology can be described.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401  (x64 on, as in every mining run)
from repro.kernels import aggregate, canonical_refine, compact, gather, radix_bin
from repro.kernels.canonical_check.canonical_check import (
    canonical_check_pallas,
    canonical_check_tiles_pallas,
    expand_canonical_pallas,
)

N, D, W = 3312, 252, 104          # citeseer_like(1.0): vertices, degree, words
CHUNK, K = 4096, 2                # chunk rows x members of a size-2 frontier
CAND = CHUNK * K * D              # candidate slots of one chunk
OUT_CAP = 1 << 16
BIN_ROWS = 1 << 16
QCAP = 4096


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to a persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


def _stream_compact(sh):
    _compile(sh, lambda k: compact.stream_compact_pallas(
        k, OUT_CAP, interpret=False), ((CAND,), jnp.bool_))


def _seg_unique(sh):
    _compile(sh, lambda n, v: aggregate.seg_unique_pallas(
        n, v, QCAP, interpret=False),
        ((BIN_ROWS,), jnp.bool_), ((BIN_ROWS,), jnp.bool_))


def _radix_sort(sh):
    _compile(sh, lambda c, v: radix_bin.radix_sort_codes(
        c, v, interpret=False),
        ((BIN_ROWS, 3), jnp.int64), ((BIN_ROWS,), jnp.bool_))


def _gather_rows(sh):
    _compile(sh, lambda t, r: gather.gather_rows_pallas(
        t, r, interpret=False),
        ((N, D), jnp.int32), ((CHUNK * K,), jnp.int32))
    _compile(sh, lambda t, r: gather.gather_rows_pallas(
        t, r, interpret=False),
        ((N, W), jnp.uint32), ((CHUNK * K,), jnp.int32))


def _canonical_check(sh):
    _compile(sh, lambda m, nv, c, a: canonical_check_pallas(
        m, nv, c, a, interpret=False),
        ((CAND, K), jnp.int32), ((CAND,), jnp.int32), ((CAND,), jnp.int32),
        ((N, W), jnp.uint32))


def _canonical_check_tiles(sh):
    _compile(sh, lambda m, r, nv, c, a: canonical_check_tiles_pallas(
        m, r, nv, c, a, interpret=False),
        ((CAND, K), jnp.int32), ((CAND, K), jnp.int32), ((CAND,), jnp.int32),
        ((CAND,), jnp.int32), ((CHUNK * K, W), jnp.uint32))


def _expand_canonical(sh):
    _compile(sh, lambda m, nv, nb, a: expand_canonical_pallas(
        m, nv, nb, a, interpret=False),
        ((CHUNK, K), jnp.int32), ((CHUNK,), jnp.int32), ((N, D), jnp.int32),
        ((N, W), jnp.uint32))


def _refine(sh):
    _compile(sh, lambda c, v: canonical_refine.refine_batch(
        c, v, (2, 3, 4), with_orbits=True, use_kernel=True,
        interpret=False),
        ((QCAP, 3), jnp.int64), ((QCAP,), jnp.bool_))


KERNELS = {
    "stream_compact_pallas": _stream_compact,
    "seg_unique_pallas": _seg_unique,
    "radix_sort_codes": _radix_sort,
    "gather_rows_pallas": _gather_rows,
    "canonical_check_pallas": _canonical_check,
    "canonical_check_tiles_pallas": _canonical_check_tiles,
    "expand_canonical_pallas": _expand_canonical,
    "refine_batch": _refine,
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel):
    KERNELS[kernel](one_chip)


def test_widths_match_the_smoke_graph():
    """The widths above are the CiteSeer-shaped graph's own."""
    from repro.core import graph as G

    g = G.citeseer_like(1.0)
    assert (g.n, int(g.degrees().max()), -(-g.n // 32)) == (N, D, W)
    assert np.asarray(g.labels).max() < 256
