"""Paged decode attention (Pallas TPU): the decode hot-spot.

TPU adaptation of vLLM's PagedAttention [survey dim 2b-i]: CUDA gathers KV
per-token through the block table with scattered loads; the TPU has no
efficient MXU-adjacent gather, so the *pages become the grid dimension* and
the block table is a SCALAR-PREFETCH operand (PrefetchScalarGridSpec). The
index_map reads ``block_table[b, p]`` to pick the physical HBM page each
grid step, so the DMA engine -- not the compute core -- performs the gather,
prefetching page p+1 while page p is in the MXU. That is the TPU-idiomatic
equivalent of the CUDA kernel's shared-memory gather loop.

Grid: (batch, kv_head, pages_per_seq); the last axis is sequential, carrying
the online-softmax state (m, l, acc) for the G grouped q-heads in VMEM
scratch. One q token per request (autoregressive decode step).

Pages are laid out HEAD-MAJOR, ``[KVH, P, page, D]``, so one grid step's
block is a dense ``(page, D)`` tile: the TPU lowering requires a block's
last two dims to be tile-aligned (8 x 128) or whole, which a page-major
``[P, page, KVH, D]`` pool can only meet by loading all KV heads per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _paged_kernel(seq_lens_ref, block_table_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page_size: int):
    b = pl.program_id(0)
    p = pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    g, d = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0, 0].astype(jnp.float32) / (d ** 0.5)       # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)                     # [page, D]
    v = v_ref[0, 0].astype(jnp.float32)                     # [page, D]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [G, page]
    pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32,
                                                   (g, page_size), 1)
    valid = pos < seq_lens_ref[b]
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    pr = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(pr, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        pr, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(p == np_ - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_table, seq_lens, *,
                    interpret: bool) -> jax.Array:
    """q: [B, H, D]; k_pages/v_pages: [KVH, P, page, D];
    block_table: [B, pages_per_seq] int32; seq_lens: [B] int32.
    Returns [B, H, D].
    """
    b, h, d = q.shape
    kvh, p_total, page, _ = k_pages.shape
    pages_per_seq = block_table.shape[1]
    assert h % kvh == 0
    g = h // kvh
    qg = q.reshape(b, kvh, g, d)

    grid = (b, kvh, pages_per_seq)
    kernel = functools.partial(_paged_kernel, page_size=page)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,            # seq_lens, block_table
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d),
                             lambda bi, hi, pi, sl, bt: (bi, hi, 0, 0)),
                # the paged gather: physical page id from the block table
                pl.BlockSpec((1, 1, page, d),
                             lambda bi, hi, pi, sl, bt: (hi, bt[bi, pi], 0,
                                                         0)),
                pl.BlockSpec((1, 1, page, d),
                             lambda bi, hi, pi, sl, bt: (hi, bt[bi, pi], 0,
                                                         0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda bi, hi, pi, sl, bt: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
    )(seq_lens.astype(jnp.int32), block_table.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(b, h, d)
