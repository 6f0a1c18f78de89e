"""Causal GQA flash attention, Pallas TPU.

Blocked online-softmax attention (FlashAttention dataflow re-tiled for
VMEM/MXU): grid over (batch*kv_head*q_group, q blocks); the kernel streams
KV blocks through VMEM with running (max, sum, acc) state. Block shapes are
multiples of 128 on the contracting dims so the MXU is fully fed.

Validated in interpret mode against ref.py (the pure-jnp oracle); on real
TPU the same pallas_call lowers via Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import resolve_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, sm_scale: float,
                 causal: bool):
    """One (q-block x full-KV) program instance.

    q_ref: (BQ, D); k_ref/v_ref: (S, D); o_ref: (BQ, D).
    """
    bq, d = q_ref.shape
    s = k_ref.shape[0]
    q = q_ref[...].astype(jnp.float32) * sm_scale
    q_pos = pl.program_id(1) * bq + jax.lax.iota(jnp.int32, bq)

    def body(i, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.ds(i * block_k, block_k), :]
        v = v_ref[pl.ds(i * block_k, block_k), :]
        scores = q @ k.astype(jnp.float32).T                   # (BQ, BK)
        if causal:
            k_pos = i * block_k + jax.lax.iota(jnp.int32, block_k)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask, scores, NEG_INF)
        m_cur = jnp.maximum(m_prev, scores.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(scores - m_cur[:, None])
        l_cur = l_prev * alpha + p.sum(axis=1)
        acc = acc * alpha[:, None] + p @ v.astype(jnp.float32)
        return acc, m_cur, l_cur

    n_k = s // block_k
    if causal:
        # only KV blocks at or before this q block contribute
        n_k = jnp.minimum(n_k, (pl.program_id(1) + 1) * bq // block_k + 1)
    acc = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, _, l = jax.lax.fori_loop(0, n_k, body, (acc, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention_bhsd(
    q, k, v, causal=True, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
    interpret=None,
):
    """q: (BH, Sq, D); k/v: (BH, Sk, D) (kv heads already broadcast).
    Returns (BH, Sq, D)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    sm_scale = d ** -0.5

    kernel = functools.partial(
        _attn_kernel, block_k=block_k, sm_scale=sm_scale, causal=causal
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
