"""Decode attention over the stacked slot pool, reading only live KV (Pallas TPU).

The engine's KV pool holds every layer, slot and position: K and V are each
``[L, B, S, KVH, D]``. One decode token per row attends the first
``lens[b]`` positions of its row; a row with ``lens[b] == 0`` (an empty
slot) attends nothing and reads nothing.

One kernel call per layer. The layer index and ``lens`` are scalar-prefetch
operands, so XLA never slices a layer out of the pool: the kernel copies
``(bk, KVH, D)`` blocks of K and V straight from the pool in HBM. One loop
walks the live blocks of every row with a double-buffered DMA, the next
block (of this row, or the first of the next live row) in flight while this
one is scored, so blocks past a row's length are never copied and a dead
row costs one scalar test. ``block_len`` picks ``bk`` from the observable
``KVH * D`` so that every copy moves about ``BLOCK_BYTES``.

A block is read as ``bk * KVH`` rows of ``D`` in the pool's own tiling (no
relayout), and all ``H = KVH * G`` q-heads of the row are scored against all
of its rows in one matmul; row ``c`` of the block belongs to KV head
``c % KVH``, so a q-head's scores on another head's rows are masked like the
positions past the length. The MXU streams each K and V row once, as it
would head by head. The softmax runs online in float32 (``m``, ``l``,
``acc`` in VMEM, as in ``paged_attention.py``); the matmul operands stay in
the pool's dtype with float32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
BLOCK_BYTES = 256 * 1024        # bytes of one K block (and one V block)


def block_len(cache_len: int, kv_heads: int, head_dim: int,
              itemsize: int) -> int:
    """Positions per block: the largest divisor of ``cache_len`` whose K
    block holds at most ``BLOCK_BYTES``, with ``bk * KVH`` a whole number
    of sublane tiles (16 rows); the whole row where no divisor qualifies."""
    cap = BLOCK_BYTES // (kv_heads * head_dim * itemsize)
    fits = [d for d in range(1, min(cap, cache_len) + 1)
            if cache_len % d == 0 and (d * kv_heads) % 16 == 0]
    return max(fits) if fits else cache_len


def blocks_read(lens, block: int) -> int:
    """KV blocks the kernel copies for one layer (each a K and a V block)."""
    return int(sum(-(-int(n) // block) for n in lens))


def _kernel(layer_ref, lens_ref, q_ref, col_head_ref, q_head_ref, k_hbm,
            v_hbm, o_ref, k_buf, v_buf, sem, m_ref, l_ref, acc_ref, *,
            block: int, scale: float):
    n_rows = lens_ref.shape[0]
    kvh, d = k_buf.shape[2], k_buf.shape[3]
    layer = layer_ref[0]
    # one pass for bf16 operands whatever the ambient precision (Mosaic
    # refuses HIGHEST there); a float32 pool multiplies at full precision
    precision = (jax.lax.Precision.HIGHEST if k_buf.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    # a row that attends nothing still has an output: zeros, never garbage
    o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32).astype(o_ref.dtype)

    def n_blocks(r):
        return (lens_ref[r] + block - 1) // block

    def next_live(r):
        """The first row from ``r`` on that attends something, or n_rows."""
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < n_rows, lens_ref[jnp.minimum(r, n_rows - 1)] == 0),
            lambda r: r + 1, r)

    def copies(r, j, slot):
        src = pl.ds(j * block, block)
        return (pltpu.make_async_copy(k_hbm.at[layer, r, src],
                                      k_buf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, r, src],
                                      v_buf.at[slot], sem.at[1, slot]))

    def start(r, j, slot):
        for c in copies(r, j, slot):
            c.start()

    first = next_live(0)

    @pl.when(first < n_rows)
    def _prefetch_first():
        start(first, 0, 0)

    def body(carry):
        r, j, slot = carry
        last = j + 1 == n_blocks(r)
        nr = jnp.where(last, next_live(r + 1), r)
        nj = jnp.where(last, 0, j + 1)

        @pl.when(nr < n_rows)
        def _prefetch_next():
            start(nr, nj, 1 - slot)

        for c in copies(r, j, slot):
            c.wait()

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        k = k_buf.at[slot].reshape(block * kvh, d)[...]
        v = v_buf.at[slot].reshape(block * kvh, d)[...]
        q = q_ref[r].astype(k.dtype)                           # [H, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32) * scale
        # block row c is position j*bk + c // KVH of KV head c % KVH
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = jnp.logical_and(col_head_ref[...] == q_head_ref[...],
                                col < (lens_ref[r] - j * block) * kvh)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(last)
        def _finish():
            o_ref[r] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

        return nr, nj, 1 - slot

    jax.lax.while_loop(lambda c: c[0] < n_rows, body,
                       (first, jnp.int32(0), jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def pool_decode_attention(q, k_pool, v_pool, layer, lens, *,
                          block: int | None = None,
                          interpret: bool) -> jax.Array:
    """q: [B, H, D], one token a row; k_pool, v_pool: [L, B, S, KVH, D];
    layer: int32 scalar; lens: [B] int32, the positions each row attends
    (0 for a row that attends nothing). Returns [B, H, D] in q's dtype,
    zeros for a row with ``lens`` 0."""
    b, h, d = q.shape
    _, _, s_len, kvh, _ = k_pool.shape
    if block is None:
        block = block_len(s_len, kvh, d, k_pool.dtype.itemsize)
    rows = block * kvh
    col_head = np.tile(np.arange(kvh, dtype=np.int32), block)[None]
    q_head = (np.arange(h, dtype=np.int32) // (h // kvh))[:, None]
    kernel = functools.partial(_kernel, block=block, scale=1.0 / d ** 0.5)
    whole = lambda i, layer, lens: (0, 0, 0)              # noqa: E731
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                # layer, lens
            grid=(1,),
            in_specs=[
                pl.BlockSpec((b, h, d), whole),
                pl.BlockSpec((1, rows), lambda i, layer, lens: (0, 0)),
                pl.BlockSpec((h, 1), lambda i, layer, lens: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),            # K pool, HBM
                pl.BlockSpec(memory_space=pl.ANY),            # V pool, HBM
            ],
            out_specs=pl.BlockSpec((b, h, d), whole),
            scratch_shapes=[
                pltpu.VMEM((2, block, kvh, d), k_pool.dtype),
                pltpu.VMEM((2, block, kvh, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((h, 1), jnp.float32),              # m
                pltpu.VMEM((h, 1), jnp.float32),              # l
                pltpu.VMEM((h, d), jnp.float32),              # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lens.astype(jnp.int32),
      q, jnp.asarray(col_head), jnp.asarray(q_head), k_pool, v_pool)
