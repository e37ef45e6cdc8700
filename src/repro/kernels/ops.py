"""Public jit'd kernel entry points with shape checks + backend dispatch.

``interpret_mode()`` is the one place the Pallas interpret choice is made:
on a TPU backend the kernels compile natively (interpret=False); on any
other backend they run in interpret mode. Callers that want XLA-compiled
speed off the TPU can force the pure-jnp reference (``impl='ref'``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.paged_attention import paged_attention as _paged


def interpret_mode() -> bool:
    """True unless the default backend is a TPU (Pallas interpret mode)."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    kv_len: int | None = None, impl: str = "auto"):
    """GQA flash attention. q [B,H,Sq,D]; k,v [B,KVH,Sk,D] -> [B,H,Sq,D]."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("flash_attention expects rank-4 q/k/v")
    if k.shape != v.shape:
        raise ValueError(f"k/v shape mismatch: {k.shape} vs {v.shape}")
    if q.shape[0] != k.shape[0] or q.shape[3] != k.shape[3]:
        raise ValueError(f"q/k incompatible: {q.shape} vs {k.shape}")
    if q.shape[1] % k.shape[1]:
        raise ValueError("H must be a multiple of KVH")
    if impl == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal,
                                        kv_len=kv_len, window=window)
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, kv_len=kv_len, interpret=interpret_mode())


def paged_attention(q, k_pages, v_pages, block_table, seq_lens, *,
                    impl: str = "auto"):
    """Paged decode attention over head-major pages [KVH,P,page,D].
    q [B,H,D] -> [B,H,D]."""
    if q.ndim != 3 or k_pages.ndim != 4:
        raise ValueError("paged_attention expects q rank-3, pages rank-4")
    if k_pages.shape != v_pages.shape:
        raise ValueError("k_pages/v_pages shape mismatch")
    if block_table.ndim != 2 or block_table.shape[0] != q.shape[0]:
        raise ValueError("block_table must be [B, pages_per_seq]")
    if q.shape[1] % k_pages.shape[0]:
        raise ValueError("H must be a multiple of KVH")
    if impl == "ref":
        return _ref.paged_attention_ref(q, k_pages, v_pages, block_table,
                                        seq_lens)
    return _paged(q, k_pages, v_pages, block_table, seq_lens,
                  interpret=interpret_mode())
