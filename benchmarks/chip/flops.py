"""Operations and bytes a serving call needs, from the configuration alone.

These count the work the result needs, not the work the program happens
to do: a prefill unembeds only its last position, a decode step reads
the key/value entries of its live positions only, and padding, empty
slots and full-sequence logits count for nothing. A program that removes
such waste therefore raises its share of the roofline, and none can push
it past the bound these counts set.

``m`` is the ``model`` object of a configuration file (published key
names). Weights are counted at ``BYTES_PER_PARAM`` (bfloat16, as served).
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

BYTES_PER_PARAM = 2
KV_BYTES = 2


def _dims(m: Dict) -> Tuple[int, int, int, int, int, int, int]:
    return (m["num_hidden_layers"], m["hidden_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["intermediate_size"], m["vocab_size"])


def layer_matmul_params(m: Dict) -> int:
    """Weights of one decoder layer's matrix products (norms excluded)."""
    _, d, h, k, hd, ff, _ = _dims(m)
    return d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * ff


def projector_params(m: Dict) -> int:
    return 2 * m["hidden_size"] ** 2 if m.get("visual_tokens") else 0


def kv_bytes_per_token(m: Dict) -> int:
    n_layers, _, _, k, hd, _, _ = _dims(m)
    return n_layers * 2 * k * hd * KV_BYTES


def prefill_cost(m: Dict, n_text: int, n_visual: int) -> Tuple[float, float]:
    """(operations, bytes) of one prefill of ``n_visual`` (post-compression)
    visual and ``n_text`` text tokens, unembedding the last position."""
    n_layers, d, h, _, hd, _, vocab = _dims(m)
    s = n_text + n_visual
    ops = 2.0 * s * n_layers * layer_matmul_params(m)
    # causal scores and weighted values: s(s+1)/2 query-key pairs a head
    ops += 2.0 * 2.0 * h * hd * (s * (s + 1) / 2.0) * n_layers
    ops += 2.0 * n_visual * projector_params(m)
    ops += 2.0 * d * vocab
    weights = n_layers * layer_matmul_params(m) + d * vocab
    if n_visual:
        weights += projector_params(m)
    nbytes = (weights * BYTES_PER_PARAM
              + s * kv_bytes_per_token(m)          # the cache it writes
              + s * d * BYTES_PER_PARAM)           # the embeddings it reads
    return ops, float(nbytes)


def decode_cost(m: Dict, contexts: Sequence[int]) -> Tuple[float, float]:
    """(operations, bytes) of one decode step over live rows whose
    contexts (positions attended, the new token's included) are given."""
    n_layers, d, h, _, hd, _, vocab = _dims(m)
    n = len(contexts)
    ctx = float(sum(contexts))
    ops = 2.0 * n * (n_layers * layer_matmul_params(m) + d * vocab)
    ops += 2.0 * 2.0 * h * hd * ctx * n_layers
    nbytes = ((n_layers * layer_matmul_params(m) + d * vocab)
              * BYTES_PER_PARAM + ctx * kv_bytes_per_token(m))
    return ops, float(nbytes)


def least_time(ops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def window_ops(m: Dict, n_visual: int, records, t0: float,
               t1: float) -> Tuple[float, float]:
    """(prefill, decode) operations of the tokens delivered in [t0, t1]:
    a first token costs its request's prefill, every later token one
    decode row at its context."""
    pre = dec = 0.0
    per_row = decode_cost(m, [0])[0]
    attn = 2.0 * 2.0 * m["num_attention_heads"] * m["head_dim"] \
        * m["num_hidden_layers"]
    for r in records:
        n_text = len(r.plan.tokens)
        nv = n_visual if r.plan.image is not None else 0
        for j, t in enumerate(r.times):
            if not t0 <= t <= t1:
                continue
            if j == 0:
                pre += prefill_cost(m, n_text, nv)[0]
            else:
                dec += per_row + attn * (nv + n_text + j)
    return pre, dec
