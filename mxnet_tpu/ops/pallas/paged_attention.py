"""Ragged paged decode attention in Pallas (TPU).

The fused path of the serving engine's paged KV cache
(mxnet_tpu/serving/paged_cache.py; design per *Ragged Paged Attention*,
PAPERS.md arxiv 2604.15464): ONE query per sequence slot attends over
that slot's page-table-addressed KV pages with per-slot length masking —
the dense ``(S, Lmax, C)`` gathered view is never materialised.  Online
softmax (running max / sum / accumulator per head) over the page loop,
exactly the flash_attention recurrence with pages as the k blocks and the
slot's *own* ragged length as the mask, so mixed-length in-flight
requests share one kernel instance.

Forward-only (decode is inference; no vjp).  Compute is f32 regardless
of pool dtype.  Like the other kernels in this package it runs in
interpret mode off-TPU (the CPU test path) and lowers through Mosaic on
TPU.  The page table and lengths are scalar-prefetch operands
(``PrefetchScalarGridSpec``): resident in SMEM before the body runs, so
the page loop can read pool rows by dynamic index.

Shapes: q (S, H, hd); k_pool/v_pool (N, page_size, H, hd);
page_table (S, P) int32; lengths (S,) int32 (valid cache rows per slot,
0 = slot inactive -> zero output).  Returns (S, H, hd) in q's dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30

__all__ = ["paged_decode_attention"]


def _kernel(ps: int, P: int, sm_scale: float,
            table_ref, len_ref, q_ref, kpool_ref, vpool_ref, o_ref):
    s = pl.program_id(0)
    q = q_ref[0].astype(jnp.float32) * sm_scale          # (H, hd)
    H, hd = q.shape
    length = len_ref[s]

    m0 = jnp.full((H, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    a0 = jnp.zeros((H, hd), jnp.float32)

    def body(p, carry):
        m, l, acc = carry
        page = table_ref[s * P + p]
        k = kpool_ref[pl.ds(page, 1)][0].astype(jnp.float32)  # (ps, H, hd)
        v = vpool_ref[pl.ds(page, 1)][0].astype(jnp.float32)
        # (H, ps) scores: batched over heads — q (H, hd) x k^T (H, hd, ps)
        kt = jnp.transpose(k, (1, 2, 0))                      # (H, hd, ps)
        scores = jax.lax.dot_general(
            q, kt, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)               # (H, ps)
        kpos = p * ps + jax.lax.broadcasted_iota(jnp.int32, (H, ps), 1)
        scores = jnp.where(kpos < length, scores, _NEG)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        prob = jnp.exp(scores - m_new)                        # (H, ps)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + prob.sum(axis=-1, keepdims=True)
        vt = jnp.transpose(v, (1, 0, 2))                      # (H, ps, hd)
        pv = jax.lax.dot_general(
            prob, vt, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)               # (H, hd)
        return m_new, l, acc * alpha + pv

    m, l, acc = jax.lax.fori_loop(0, P, body, (m0, l0, a0))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    # length == 0 (inactive slot): every score masked -> uniform probs
    # would leak pool garbage; force the output to zero instead
    out = jnp.where(length > 0, acc / safe_l, 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, page_table, lengths,
                           sm_scale=None):
    """softmax(q @ K_pages^T * sm_scale) @ V_pages per slot, masked to
    each slot's own ``lengths`` — see the module docstring for shapes."""
    from . import interpret
    from jax.experimental.pallas import tpu as pltpu

    S, H, hd = q.shape
    N, ps, _, _ = k_pool.shape
    P = page_table.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    # index maps receive the scalar-prefetch refs after the grid indices
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda s, *_: (s, 0, 0)),         # q
            pl.BlockSpec((N, ps, H, hd), lambda s, *_: (0, 0, 0, 0)),  # k
            pl.BlockSpec((N, ps, H, hd), lambda s, *_: (0, 0, 0, 0)),  # v
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda s, *_: (s, 0, 0)),
    )
    call = pl.pallas_call(
        functools.partial(_kernel, ps, P, float(sm_scale)),
        name="mx_paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, hd), q.dtype),
        interpret=interpret(),
    )
    return call(page_table.reshape(-1).astype(jnp.int32),
                lengths.astype(jnp.int32), q, k_pool, v_pool)
