"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        kv_len: int | None = None,
                        window: int = 0) -> jax.Array:
    """Grouped-query attention oracle.

    q: [B, H, Sq, D];  k, v: [B, KVH, Sk, D];  H = KVH * G.
    ``kv_len``: only the first kv_len keys are valid (padding mask).
    ``window`` > 0: sliding-window causal attention.
    Returns [B, H, Sq, D] in q.dtype (accumulation in f32).
    """
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    g = h // kvh
    qf = q.reshape(b, kvh, g, sq, d).astype(jnp.float32) / (d ** 0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bkgqd,bkcd->bkgqc", qf, kf)
    q_pos = jnp.arange(sq)
    k_pos = jnp.arange(sk)
    valid = jnp.ones((sq, sk), bool)
    if kv_len is not None:
        valid = valid & (k_pos[None, :] < kv_len)
    if causal:
        # decode convention: q block sits at the END of the kv sequence
        offset = (kv_len if kv_len is not None else sk) - sq
        valid = valid & (k_pos[None, :] <= q_pos[:, None] + offset)
        if window:
            valid = valid & (k_pos[None, :] > q_pos[:, None] + offset - window)
    s = jnp.where(valid[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqc,bkcd->bkgqd", p, vf)
    return o.reshape(b, h, sq, d).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, block_table, seq_lens
                        ) -> jax.Array:
    """Decode attention over a paged KV pool, oracle.

    q          : [B, H, D]           one query token per request
    k_pages    : [KVH, P, page, D]   physical page pool, head-major
    v_pages    : [KVH, P, page, D]
    block_table: [B, pages_per_seq]  int32 physical page ids
    seq_lens   : [B]                 int32 valid tokens per request
    Returns [B, H, D].
    """
    b, h, d = q.shape
    kvh, p_total, page, _ = k_pages.shape
    pages_per_seq = block_table.shape[1]
    g = h // kvh
    # gather the logical KV for each request: [KVH, B, pages*page, D]
    k_log = k_pages[:, block_table].reshape(kvh, b, pages_per_seq * page, d)
    v_log = v_pages[:, block_table].reshape(kvh, b, pages_per_seq * page, d)
    qf = q.reshape(b, kvh, g, d).astype(jnp.float32) / (d ** 0.5)
    s = jnp.einsum("bkgd,kbcd->bkgc", qf, k_log.astype(jnp.float32))
    pos = jnp.arange(pages_per_seq * page)
    valid = pos[None] < seq_lens[:, None]                   # [B, C]
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgc,kbcd->bkgd", p, v_log.astype(jnp.float32))
    return o.reshape(b, h, d).astype(q.dtype)


def rmsnorm_ref(x, scale, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)
