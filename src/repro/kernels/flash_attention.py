"""Tiled causal GQA flash attention (Pallas TPU): the prefill hot-spot.

TPU adaptation of FlashAttention [survey dim 3c]: the CUDA version's
SRAM-resident tiling + warp specialization becomes BlockSpec VMEM tiling
over a 4D grid (batch, q-head, q-block, kv-block). The last grid dimension
is sequential on TPU ("arbitrary" semantics), so the online-softmax running
state (m, l, acc) lives in VMEM scratch carried across kv-blocks --
HBM<->VMEM movement is the implicit DMA pipeline pallas_call builds from the
BlockSpecs, replacing FA-3's explicit TMA/warp-specialization overlap.

Block shapes default to (128, 128): MXU-aligned (multiples of 128 in both
matmul dims) and small enough that q/k/v/acc tiles fit VMEM comfortably:
  bq*D + bk*D (k) + bk*D (v) + bq*bk (s) + bq*D (acc) floats
  = 128*128 * 5 * 4B = 320 KiB << 16 MiB VMEM for D=128.

GQA: the q-head grid axis maps to kv-head ``h // group`` in the k/v
index_map -- each kv tile is re-read by its group's q heads (XLA would
materialize the broadcast; here it is just an index computation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int, block_q: int,
                  block_k: int, kv_len: int, q_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bk, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bk]

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0) \
        + q_offset
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    valid = k_pos < kv_len
    if causal:
        valid = valid & (k_pos <= q_pos)
        if window:
            valid = valid & (k_pos > q_pos - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                   # [bq, 1]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "kv_len", "q_offset", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    kv_len: int | None = None, q_offset: int = 0,
                    interpret: bool) -> jax.Array:
    """q: [B, H, Sq, D]; k, v: [B, KVH, Sk, D]. Returns [B, H, Sq, D].

    Sq/Sk are padded to block multiples internally; ``kv_len`` marks valid
    keys (defaults to Sk). ``q_offset``: absolute position of q[...,0,:]
    for causal masking (chunked prefill / decode-block use).
    ``interpret=True`` executes the kernel body in the Pallas interpreter;
    ``repro.kernels.ops.interpret_mode()`` chooses it by backend.
    """
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    assert h % kvh == 0, "q heads must be a multiple of kv heads"
    group = h // kvh
    kv_len = sk if kv_len is None else kv_len
    if causal and q_offset == 0 and sq < kv_len:
        q_offset = kv_len - sq          # decode-block convention

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sq_p, sk_p = sq + pad_q, sk + pad_k

    grid = (b, h, sq_p // block_q, sk_p // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=1.0 / (d ** 0.5), causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_len=kv_len, q_offset=q_offset)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),       # acc
            pltpu.VMEM((block_q, 1), jnp.float32),       # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),       # l (running sum)
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq]
