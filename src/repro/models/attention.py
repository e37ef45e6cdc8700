"""Attention: GQA/MQA full + sliding-window + cache decode, MLA (DeepSeek).

Three execution paths per layer:
  * ``full_attention``     -- training / prefill, blockwise (flash-style)
                              online-softmax over KV blocks; causal or
                              bidirectional; optional sliding window.
  * ``prefill_into_cache`` -- prefill that also materializes the KV cache.
  * ``decode_attention``   -- one token vs a cache (full or ring-buffer
                              window). On the engine's stacked pool on a
                              TPU it runs kernels/decode_attention, which
                              reads only each row's live positions.

GQA is computed grouped (q reshaped [B,S,K,G,D]) so KV heads are never
materialized repeated -- this matters for both HLO bytes and the roofline.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import pool_decode_attention
from repro.kernels.ops import interpret_mode
from repro.models.layers import ParamSpec, spec, apply_rope

NEG_INF = -1e30


# --------------------------------------------------------------------------
# specs
# --------------------------------------------------------------------------

def attn_specs(cfg) -> Dict[str, ParamSpec]:
    d, h, k, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if cfg.use_mla:
        qk_hd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        out = {
            "wkv_a": spec((d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                          ("embed", None)),
            "kv_norm": spec((cfg.kv_lora_rank,), (None,), init="ones"),
            "wk_b": spec((cfg.kv_lora_rank, h, cfg.qk_nope_head_dim),
                         (None, "heads", None)),
            "wv_b": spec((cfg.kv_lora_rank, h, cfg.v_head_dim),
                         (None, "heads", None)),
            "wo": spec((h, cfg.v_head_dim, d), ("heads", None, "embed")),
        }
        if cfg.q_lora_rank:
            out["wq_a"] = spec((d, cfg.q_lora_rank), ("embed", None))
            out["q_norm"] = spec((cfg.q_lora_rank,), (None,), init="ones")
            out["wq_b"] = spec((cfg.q_lora_rank, h, qk_hd),
                               (None, "heads", None))
        else:
            out["wq"] = spec((d, h, qk_hd), ("embed", "heads", None))
        return out
    return {
        "wq": spec((d, h, hd), ("embed", "heads", None)),
        "wk": spec((d, k, hd), ("embed", "kv_heads", None)),
        "wv": spec((d, k, hd), ("embed", "kv_heads", None)),
        "wo": spec((h, hd, d), ("heads", None, "embed")),
    }


def cross_attn_specs(cfg) -> Dict[str, ParamSpec]:
    return attn_specs(cfg)


# --------------------------------------------------------------------------
# core grouped SDPA, blockwise over KV (flash-style online softmax)
# --------------------------------------------------------------------------

def _grouped(q, num_kv: int):
    """[B,S,H,D] -> [B,S,K,G,D]."""
    b, s, h, d = q.shape
    return q.reshape(b, s, num_kv, h // num_kv, d)


def blockwise_sdpa(q, k, v, *, q_pos, k_pos, causal: bool,
                   window: int = 0, block_k: int = 1024,
                   bias: Optional[jax.Array] = None):
    """Grouped-query flash-style attention in pure jnp.

    q: [B,Sq,K,G,D]; k,v: [B,Sk,K,D]; q_pos [Sq], k_pos [Sk] absolute
    positions (int32) used for causal/window masking (k_pos < 0 = invalid
    slot). Online softmax over KV blocks keeps peak memory at
    O(Sq * block_k) instead of O(Sq * Sk).
    """
    b, sq, kh, g, d = q.shape
    sk = k.shape[1]
    dv = v.shape[-1]
    scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32) * scale

    nblocks = max(1, (sk + block_k - 1) // block_k)
    pad = nblocks * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-1)
    kb = k.reshape(b, nblocks, block_k, kh, d)
    vb = v.reshape(b, nblocks, block_k, kh, dv)
    kpb = k_pos.reshape(nblocks, block_k)

    def step(carry, blk):
        m, l, acc = carry
        kblk, vblk, kp = blk
        s = jnp.einsum("bqkgd,bckd->bkgqc", qf, kblk.astype(jnp.float32))
        valid = kp[None, :] >= 0
        if causal:
            valid = valid & (kp[None, :] <= q_pos[:, None])
        if window:
            valid = valid & (kp[None, :] > q_pos[:, None] - window)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        if bias is not None:
            s = s + bias
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bckd->bkgqd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kh, g, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), kpb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # [B,K,G,Sq,Dv] -> [B,Sq,K*G,Dv]
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, kh * g, dv)
    return out.astype(q.dtype)


def simple_sdpa(q, k, v, *, q_pos, k_pos, causal: bool, window: int = 0):
    """One-shot grouped SDPA (decode / tiny seqs): q [B,Sq,K,G,D].

    q_pos [B,Sq] or [Sq]; k_pos [B,Sk] or [Sk] (per-request ragged decode
    positions supported -- continuous batching needs them).
    """
    b, sq, kh, g, d = q.shape
    dv = v.shape[-1]
    sk = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    q_pos = jnp.broadcast_to(jnp.atleast_1d(q_pos), (b, sq)) \
        if q_pos.ndim <= 1 else q_pos
    k_pos = jnp.broadcast_to(jnp.atleast_1d(k_pos), (b, sk)) \
        if k_pos.ndim <= 1 else k_pos
    s = jnp.einsum("bqkgd,bckd->bkgqc", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    valid = k_pos[:, None, :] >= 0                              # [B,Sq,Sk]
    if causal:
        valid = valid & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window:
        valid = valid & (k_pos[:, None, :] > q_pos[:, :, None] - window)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqc,bckd->bkgqd", p, v.astype(jnp.float32))
    return jnp.moveaxis(out, 3, 1).reshape(b, sq, kh * g, dv).astype(q.dtype)


# --------------------------------------------------------------------------
# standard GQA layer
# --------------------------------------------------------------------------

def qkv_proj(p, x):
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    k = jnp.einsum("bsd,dke->bske", x, p["wk"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("bsd,dke->bske", x, p["wv"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return q, k, v


def out_proj(p, o):
    return jnp.einsum("bshe,hed->bsd", o, p["wo"],
                      preferred_element_type=jnp.float32).astype(o.dtype)


def full_attention(p, x, cos, sin, cfg, *, causal=True, window=0,
                   positions=None, block_k=1024):
    """Training/prefill attention (no cache returned)."""
    b, s, _ = x.shape
    q, k, v = qkv_proj(p, x)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    pos = positions if positions is not None else jnp.arange(s, dtype=jnp.int32)
    qg = _grouped(q, cfg.num_kv_heads)
    o = blockwise_sdpa(qg, k, v, q_pos=pos, k_pos=pos, causal=causal,
                       window=window, block_k=block_k)
    return out_proj(p, o)


# ---------------------------- KV cache ------------------------------------

def kv_cache_specs(cfg, batch: int, cache_len: int, windowed: bool):
    """ParamSpec tree for one layer's cache (shape + logical axes)."""
    k = cfg.num_kv_heads
    hd = cfg.head_dim
    length = min(cache_len, cfg.sliding_window) if windowed else cache_len
    if cfg.use_mla:
        tree = {
            "ckv": spec((batch, length, cfg.kv_lora_rank),
                        ("batch", "cache_seq", None), init="zeros"),
            "k_rope": spec((batch, length, cfg.qk_rope_head_dim),
                           ("batch", "cache_seq", None), init="zeros"),
        }
    else:
        tree = {
            "k": spec((batch, length, k, hd),
                      ("batch", "cache_seq", "kv_heads", None), init="zeros"),
            "v": spec((batch, length, k, hd),
                      ("batch", "cache_seq", "kv_heads", None), init="zeros"),
        }
    if windowed:
        tree["slot_pos"] = spec((batch, length), ("batch", "cache_seq"),
                                init="zeros", dtype="int32")
    return tree


def init_kv_cache(cfg, batch, cache_len, windowed, dtype):
    specs = kv_cache_specs(cfg, batch, cache_len, windowed)

    def _one(path, s):
        dt = jnp.dtype(s.dtype or dtype)
        arr = jnp.zeros(s.shape, dt)
        if path[-1] == "slot_pos":
            arr = arr - 1  # -1 = empty slot
        return arr
    from repro.models.layers import tree_map_specs
    return tree_map_specs(_one, specs)


def _cache_write_prefill(cache, new_k, new_v, windowed):
    """Write the whole prompt starting at position 0."""
    length = cache["k"].shape[1]
    b, s_new = new_k.shape[0], new_k.shape[1]
    if windowed:
        # keep only the last ``length`` entries if the prompt overflows
        take = min(s_new, length)
        src_k, src_v = new_k[:, -take:], new_v[:, -take:]
        pos0 = s_new - take
        idx = jnp.mod(pos0 + jnp.arange(take), length)
        k = cache["k"].at[:, idx].set(src_k)
        v = cache["v"].at[:, idx].set(src_v)
        sp = cache["slot_pos"].at[:, idx].set(
            (pos0 + jnp.arange(take, dtype=jnp.int32))[None])
        return dict(cache, k=k, v=v, slot_pos=sp)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], new_k, 0, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], new_v, 0, axis=1)
    return dict(cache, k=k, v=v)


def write_decode_rows(cache, new, pos, layer=None):
    """Write ONE token per request, ``new[name] [B,...]``, at per-request
    position ``pos [B]``: into one layer's cache [B,S,...], or, given
    ``layer``, into the stacked cache [L,B,S,...] at (layer, row, slot).
    Either way only B rows are written, so a donated pool is updated in
    place. A ring (``slot_pos`` in the cache) records ``pos`` per slot."""
    lead = () if layer is None else (layer,)
    windowed = "slot_pos" in cache
    length = cache[next(iter(new))].shape[len(lead) + 1]
    slot = jnp.mod(pos, length) if windowed else pos
    idx = lead + (jnp.arange(pos.shape[0]), slot)
    if windowed:
        new = dict(new, slot_pos=pos.astype(jnp.int32))
    return dict(cache, **{n: cache[n].at[idx].set(v) for n, v in new.items()})


def _layer_of(cache, layer):
    """One layer's cache: ``cache`` itself, or layer ``layer`` of a stack."""
    if layer is None:
        return cache
    return {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for n, a in cache.items()}


def prefill_into_cache(p, x, cos, sin, cfg, cache, *, window=0,
                       positions=None, block_k=1024):
    """Prefill attention that also fills the cache starting at pos 0."""
    b, s, _ = x.shape
    q, k, v = qkv_proj(p, x)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    pos = positions if positions is not None else jnp.arange(s, dtype=jnp.int32)
    windowed = "slot_pos" in cache
    cache = _cache_write_prefill(cache, k, v, windowed)
    qg = _grouped(q, cfg.num_kv_heads)
    o = blockwise_sdpa(qg, k, v, q_pos=pos, k_pos=pos, causal=True,
                       window=window, block_k=block_k)
    return out_proj(p, o), cache


def _extend_positions(start, s_new: int):
    """Positions written by an extend: start scalar -> [1,S_new] (shared by
    the batch); start [B] -> [B,S_new] per-request block offsets (batched
    speculative verify)."""
    start = jnp.asarray(start, jnp.int32)
    pos = start[..., None] + jnp.arange(s_new, dtype=jnp.int32)
    return pos[None] if pos.ndim == 1 else pos


def _cache_write_extend(cache, new_k, new_v, start, windowed):
    """Write S_new entries at offset ``start`` -- scalar (chunked prefill /
    prefix-cache continuation) or [B] per-request starts (batched
    speculative block verify). Per-request rows routed past the end are
    clipped onto the last position, the engine's reserved scratch slot."""
    length = cache["k"].shape[1]
    s_new = new_k.shape[1]
    if jnp.asarray(start).ndim:                  # per-request starts [B]
        pos = _extend_positions(start, s_new)    # [B, S_new]
        idx = jnp.mod(pos, length) if windowed \
            else jnp.clip(pos, 0, length - 1)
        bidx = jnp.arange(new_k.shape[0])[:, None]
        k = cache["k"].at[bidx, idx].set(new_k)
        v = cache["v"].at[bidx, idx].set(new_v)
        if windowed:
            sp = cache["slot_pos"].at[bidx, idx].set(pos)
            return dict(cache, k=k, v=v, slot_pos=sp)
        return dict(cache, k=k, v=v)
    if windowed:
        idx = jnp.mod(start + jnp.arange(s_new), length)
        k = cache["k"].at[:, idx].set(new_k)
        v = cache["v"].at[:, idx].set(new_v)
        sp = cache["slot_pos"].at[:, idx].set(
            (start + jnp.arange(s_new, dtype=jnp.int32))[None])
        return dict(cache, k=k, v=v, slot_pos=sp)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], new_k, start, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], new_v, start, axis=1)
    return dict(cache, k=k, v=v)


def append_attention(p, x, cos, sin, cfg, cache, start, *, window=0):
    """Multi-token cache continuation: x [B,S_new,d] appended at ``start``
    (scalar, or [B] per-request starts); attends causally against the whole
    cache (prefix + chunk).

    Enables Sarathi-style chunked prefill, RadixAttention prefix reuse, and
    batched speculative block verification on the dense-slot engine."""
    b, s_new, _ = x.shape
    q, k, v = qkv_proj(p, x)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    windowed = "slot_pos" in cache
    cache = _cache_write_extend(cache, k, v, start, windowed)
    k_pos = (cache["slot_pos"] if windowed
             else jnp.arange(cache["k"].shape[1], dtype=jnp.int32))
    q_pos = _extend_positions(start, s_new)
    qg = _grouped(q, cfg.num_kv_heads)
    o = simple_sdpa(qg, cache["k"], cache["v"], q_pos=q_pos,
                    k_pos=k_pos, causal=True, window=window)
    return out_proj(p, o), cache


def mla_append_attention(p, x, cos, sin, cfg, cache, start, *, window=0):
    """MLA chunk continuation against the latent cache. ``start`` scalar or
    [B] per-request block offsets (batched speculative verify)."""
    b, s_new, _ = x.shape
    q_nope, q_rope = _mla_q(p, x, cfg, cos, sin)
    ckv_t, k_rope_t = _mla_latent(p, x, cfg, cos, sin)
    windowed = "slot_pos" in cache
    length = cache["ckv"].shape[1]
    if jnp.asarray(start).ndim:              # per-request starts [B]
        pos = _extend_positions(start, s_new)
        idx = jnp.mod(pos, length) if windowed \
            else jnp.clip(pos, 0, length - 1)
        bidx = jnp.arange(b)[:, None]
        cache = dict(cache,
                     ckv=cache["ckv"].at[bidx, idx].set(ckv_t),
                     k_rope=cache["k_rope"].at[bidx, idx].set(k_rope_t))
        if windowed:
            cache = dict(cache,
                         slot_pos=cache["slot_pos"].at[bidx, idx].set(pos))
        k_pos = (cache["slot_pos"] if windowed
                 else jnp.arange(length, dtype=jnp.int32)[None])
    elif windowed:
        idx = jnp.mod(start + jnp.arange(s_new), length)
        cache = dict(cache,
                     ckv=cache["ckv"].at[:, idx].set(ckv_t),
                     k_rope=cache["k_rope"].at[:, idx].set(k_rope_t),
                     slot_pos=cache["slot_pos"].at[:, idx].set(
                         (start + jnp.arange(s_new, dtype=jnp.int32))[None]))
        k_pos = cache["slot_pos"]
    else:
        cache = dict(cache,
                     ckv=jax.lax.dynamic_update_slice_in_dim(
                         cache["ckv"], ckv_t, start, axis=1),
                     k_rope=jax.lax.dynamic_update_slice_in_dim(
                         cache["k_rope"], k_rope_t, start, axis=1))
        k_pos = jnp.arange(length, dtype=jnp.int32)[None]
    # naive (non-absorbed) form over the latent cache
    k_nope = jnp.einsum("bsr,rhe->bshe", cache["ckv"], p["wk_b"],
                        preferred_element_type=jnp.float32).astype(x.dtype)
    vfull = jnp.einsum("bsr,rhe->bshe", cache["ckv"], p["wv_b"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
    h = cfg.num_heads
    kr = jnp.broadcast_to(cache["k_rope"][:, :, None, :],
                          k_nope.shape[:2] + (h, cfg.qk_rope_head_dim))
    kfull = jnp.concatenate([k_nope, kr], -1)
    # MLA "kv heads" = all heads; fold K into head axis with G=1
    b_, sk = kfull.shape[0], kfull.shape[1]
    kflat = kfull
    q = jnp.concatenate([q_nope, q_rope], -1)
    qg = q.reshape(b_, s_new, h, 1, q.shape[-1])
    q_pos = _extend_positions(start, s_new)
    o = simple_sdpa(qg, kflat, vfull, q_pos=q_pos, k_pos=k_pos,
                    causal=True, window=window)
    out = jnp.einsum("bshe,hed->bsd", o, p["wo"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return out, cache


def _pool_kernel_backend() -> bool:
    """The stacked-pool decode runs the Pallas kernel on a TPU only;
    elsewhere its interpreter would be far slower than XLA."""
    return jax.default_backend() == "tpu"


def decode_attention(p, x, cos, sin, cfg, cache, pos, *, window=0,
                     layer=None, lens=None):
    """One-token decode vs cache. x [B,1,d]; pos [B] per-request int32.

    ``cache`` is one layer's, or with ``layer`` the stacked cache; the new
    token is written first (``write_decode_rows``), then attended. On a
    stacked full-length cache on a TPU, ``pool_decode_attention`` attends
    the first ``lens [B]`` positions of each row (default ``pos + 1``; 0
    for a row whose output is ignored) and reads no other KV."""
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos, jnp.int32)), (b,))
    q, k, v = qkv_proj(p, x)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    cache = write_decode_rows(cache, {"k": k[:, 0], "v": v[:, 0]}, pos, layer)
    if (layer is not None and "slot_pos" not in cache and not window
            and _pool_kernel_backend()):
        o = pool_decode_attention(
            q[:, 0], cache["k"], cache["v"], layer,
            pos + 1 if lens is None else lens,
            interpret=interpret_mode())
        return out_proj(p, o[:, None]), cache
    lc = _layer_of(cache, layer)
    k_pos = (lc["slot_pos"] if "slot_pos" in lc
             else jnp.arange(lc["k"].shape[1], dtype=jnp.int32))
    qg = _grouped(q, cfg.num_kv_heads)
    o = simple_sdpa(qg, lc["k"], lc["v"], q_pos=pos[:, None],
                    k_pos=k_pos, causal=True, window=window)
    return out_proj(p, o), cache


# --------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# --------------------------------------------------------------------------

def _mla_q(p, x, cfg, cos, sin):
    if cfg.q_lora_rank:
        ql = jnp.einsum("bsd,dr->bsr", x, p["wq_a"],
                        preferred_element_type=jnp.float32)
        ql = _rms(ql, p["q_norm"]).astype(x.dtype)
        q = jnp.einsum("bsr,rhe->bshe", ql, p["wq_b"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_head_dim:], cos, sin)
    return q_nope, q_rope


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _mla_latent(p, x, cfg, cos, sin):
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"],
                    preferred_element_type=jnp.float32)
    ckv = _rms(kv[..., :cfg.kv_lora_rank], p["kv_norm"]).astype(x.dtype)
    k_rope = kv[..., cfg.kv_lora_rank:].astype(x.dtype)
    # rope applied to the shared (MQA-style) rope key
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    return ckv, k_rope


def mla_full_attention(p, x, cos, sin, cfg, *, window=0, positions=None,
                       block_k=1024, cache=None):
    """Naive (non-absorbed) MLA for train/prefill; optionally fills cache."""
    b, s, _ = x.shape
    q_nope, q_rope = _mla_q(p, x, cfg, cos, sin)
    ckv, k_rope = _mla_latent(p, x, cfg, cos, sin)
    if cache is not None:
        windowed = "slot_pos" in cache
        if windowed:
            length = cache["ckv"].shape[1]
            take = min(s, length)
            idx = jnp.mod((s - take) + jnp.arange(take), length)
            cache = dict(cache,
                         ckv=cache["ckv"].at[:, idx].set(ckv[:, -take:]),
                         k_rope=cache["k_rope"].at[:, idx].set(
                             k_rope[:, -take:]),
                         slot_pos=cache["slot_pos"].at[:, idx].set(
                             ((s - take)
                              + jnp.arange(take, dtype=jnp.int32))[None]))
        else:
            cache = dict(cache,
                         ckv=jax.lax.dynamic_update_slice_in_dim(
                             cache["ckv"], ckv, 0, axis=1),
                         k_rope=jax.lax.dynamic_update_slice_in_dim(
                             cache["k_rope"], k_rope, 0, axis=1))
    k_nope = jnp.einsum("bsr,rhe->bshe", ckv, p["wk_b"],
                        preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("bsr,rhe->bshe", ckv, p["wv_b"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    h = cfg.num_heads
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :],
                                (b, s, h, cfg.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, k_rope_b], -1)
    pos = positions if positions is not None else jnp.arange(s, dtype=jnp.int32)
    # heads ungrouped (K=H, G=1)
    qg = q.reshape(b, s, h, 1, q.shape[-1])
    o = blockwise_sdpa(qg, k, v, q_pos=pos, k_pos=pos, causal=True,
                       window=window, block_k=block_k)
    out = jnp.einsum("bshe,hed->bsd", o, p["wo"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return (out, cache) if cache is not None else out


def mla_decode_attention(p, x, cos, sin, cfg, cache, pos, *, window=0,
                         layer=None):
    """Absorbed-form MLA decode: attention runs in the latent space.

    The per-head key projection wk_b is absorbed into the query and wv_b
    into the output -- the cache holds only [B,S,r] + [B,S,rope]; this IS
    the survey's dim-2 cache compression realized architecturally.
    pos: [B] per-request int32 (or scalar, broadcast). ``cache`` and
    ``layer`` as in ``decode_attention``.
    """
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos, jnp.int32)), (b,))
    q_nope, q_rope = _mla_q(p, x, cfg, cos, sin)       # [B,1,H,*]
    ckv_t, k_rope_t = _mla_latent(p, x, cfg, cos, sin)  # [B,1,r],[B,1,rope]
    cache = write_decode_rows(
        cache, {"ckv": ckv_t[:, 0], "k_rope": k_rope_t[:, 0]}, pos, layer)
    lc = _layer_of(cache, layer)
    if "slot_pos" in lc:
        k_pos = lc["slot_pos"]                         # [B,S]
    else:
        length = lc["ckv"].shape[1]
        k_pos = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32)[None],
                                 (b, length))
    # absorb wk_b into q: [B,1,H,nope] x [r,H,nope] -> [B,1,H,r]
    q_lat = jnp.einsum("bshe,rhe->bshr", q_nope, p["wk_b"],
                       preferred_element_type=jnp.float32)
    scale = 1.0 / ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** 0.5)
    s_lat = jnp.einsum("bshr,bcr->bhsc", q_lat,
                       lc["ckv"].astype(jnp.float32))
    s_rope = jnp.einsum("bshe,bce->bhsc", q_rope.astype(jnp.float32),
                        lc["k_rope"].astype(jnp.float32))
    s = (s_lat + s_rope) * scale
    valid = (k_pos >= 0) & (k_pos <= pos[:, None])     # [B,S]
    if window:
        valid = valid & (k_pos > (pos - window)[:, None])
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhsc,bcr->bshr", pr, lc["ckv"].astype(jnp.float32))
    o = jnp.einsum("bshr,rhe->bshe", o_lat, p["wv_b"].astype(jnp.float32))
    out = jnp.einsum("bshe,hed->bsd", o.astype(x.dtype), p["wo"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return out, cache
