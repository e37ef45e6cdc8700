"""Random weights from the seed, made by the benchmark on the device.

The layout is the published decoder's (GQA attention, SwiGLU MLP, RMSNorm,
tied embeddings, and for a vision-language model the two-layer projector
the visual embeddings pass through), written as the nested dict the
program's ``init`` returns. The harness checks the two layouts agree
before it hands these weights over, so the program runs on exactly the
weights the reference reads. One jitted call makes every leaf, in the
served dtype.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

# (shape, stddev) per leaf; a stddev of None marks a norm scale, drawn
# around 1 so that a reference which ignored it would not agree
Layout = Dict[str, Tuple[Tuple[int, ...], float]]


def layout(m: Dict) -> Layout:
    n_layers, d = m["num_hidden_layers"], m["hidden_size"]
    h, k = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["head_dim"]
    ff, vocab = m["intermediate_size"], m["vocab_size"]
    out: Layout = {
        "embed/tok": ((vocab, d), 0.02),
        "final_norm/scale": ((d,), None),
        "layers/attn/wq": ((n_layers, d, h, hd), d ** -0.5),
        "layers/attn/wk": ((n_layers, d, k, hd), d ** -0.5),
        "layers/attn/wv": ((n_layers, d, k, hd), d ** -0.5),
        "layers/attn/wo": ((n_layers, h, hd, d), (h * hd) ** -0.5),
        "layers/ln1/scale": ((n_layers, d), None),
        "layers/ln2/scale": ((n_layers, d), None),
        "layers/mlp/wi_gate": ((n_layers, d, ff), d ** -0.5),
        "layers/mlp/wi_up": ((n_layers, d, ff), d ** -0.5),
        "layers/mlp/wo": ((n_layers, ff, d), ff ** -0.5),
    }
    if m.get("visual_tokens"):
        out["projector/w1"] = ((d, d), d ** -0.5)
        out["projector/w2"] = ((d, d), d ** -0.5)
    return out


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    tree: Dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


@partial(jax.jit, static_argnums=(1, 2))
def _make(key, lay: Tuple, dtype: str):
    flat = {}
    for i, (path, shape, std) in enumerate(lay):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        w = 1.0 + 0.1 * z if std is None else z * std
        flat[path] = w.astype(dtype)
    return flat


def make(m: Dict, dtype: str, seed32: int) -> Dict:
    """All weights for ``seed32`` (a 31-bit seed), on the default device.
    The key is ``rbg``: the device's own generator, quick to compile and
    to run at billions of values, and fixed by the seed on one backend."""
    lay = tuple((p, s, std) for p, (s, std) in sorted(layout(m).items()))
    return _nest(_make(jax.random.key(seed32, impl="rbg"), lay, dtype))


def same_layout(a, b) -> bool:
    """True when two trees have the same paths, shapes and dtypes."""
    sa = jax.tree_util.tree_flatten_with_path(a)
    sb = jax.tree_util.tree_flatten_with_path(b)
    if sa[1] != sb[1]:
        return False
    return all(x.shape == y.shape and x.dtype == y.dtype
               for (_, x), (_, y) in zip(sa[0], sb[0]))
