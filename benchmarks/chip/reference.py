"""Plain float32 reference of the served decoders, in ``jax.numpy``.

It imports nothing of the program. It follows the published layer
equations of both configurations (a pre-norm decoder: RMSNorm, grouped-
query attention with rotary positions, SwiGLU MLP, tied unembedding; for
the vision-language model, visual embeddings through a two-layer GELU
projector placed before the text), computed in float32 with every matrix
product at ``Precision.HIGHEST``. Where the program departs from the
published model, the reference takes the program's inputs, and the
configuration file's ``departures`` names each case (visual tokens on
1-D positions, no attention biases, the projector's tanh GELU, the L2
salience that stands in for FastV's attention). Where the program
serves another value of a published key (the norm epsilon, the share of
the head that rotates), ``departures.served`` gives it and ``m`` here
carries it (``harness.served_model``).

``quant="fp8"`` is the control: every weight and activation entering a
linear layer is rounded to float8 (e4m3, per-tensor scale for weights,
per-row for activations) before a float32 product -- the precision a
later program might be tempted to serve in.

The whole sequence (prompt and served tokens) runs at once, layer by
layer under ``lax.scan``, padded to a fixed length so one program serves
every request of a cell.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(spec, x, w, quant):
    """``einsum(spec, x, w)`` in float32; the control rounds both sides."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x = _q8(x, -1)
        w = _q8(w, None)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta, fraction):
    """Rotary embedding, rotate-half pairing, on the first ``fraction`` of
    each head (``partial_rotary_factor``); x [S, H, D], pos [S]."""
    d = int(x.shape[-1] * fraction)
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., : d // 2], x[..., d // 2: d], x[..., d:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def _layer(m, quant, x, lp):
    s = x.shape[0]
    h, k, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    eps = m["rms_norm_eps"]
    pos = jnp.arange(s)
    a = _rms(x, lp["ln1"]["scale"], eps)
    q = _linear("sd,dhe->she", a, lp["attn"]["wq"], quant)
    kk = _linear("sd,dke->ske", a, lp["attn"]["wk"], quant)
    v = _linear("sd,dke->ske", a, lp["attn"]["wv"], quant)
    frac = m["partial_rotary_factor"]
    q = _rope(q, pos, m["rope_theta"], frac)
    kk = _rope(kk, pos, m["rope_theta"], frac)
    q = q.reshape(s, k, h // k, hd)
    scores = jnp.einsum("qkgd,ckd->kgqc", q, kk, precision=HIGHEST)
    scores = scores / np.sqrt(hd)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("kgqc,ckd->qkgd", p, v, precision=HIGHEST)
    o = o.reshape(s, h, hd)
    x = x + _linear("she,hed->sd", o, lp["attn"]["wo"], quant)
    b = _rms(x, lp["ln2"]["scale"], eps)
    g = _linear("sd,df->sf", b, lp["mlp"]["wi_gate"], quant)
    u = _linear("sd,df->sf", b, lp["mlp"]["wi_up"], quant)
    x = x + _linear("sf,fd->sd", jax.nn.silu(g) * u, lp["mlp"]["wo"], quant)
    return x, None


@partial(jax.jit, static_argnums=(0, 1))
def _hidden(mkey, quant, params, ids, visual):
    """Final normed hidden states [L, d] of ``visual`` (raw embeddings,
    [nv, d], or a [0, d] array) followed by the token ``ids`` [L]."""
    m = dict(mkey)
    emb = params["embed"]["tok"]
    x = jnp.take(emb, ids, axis=0).astype(jnp.float32)
    if visual.shape[0]:
        pj = params["projector"]
        vp = jax.nn.gelu(_linear("nd,de->ne", visual, pj["w1"], quant),
                         approximate=True)
        vp = _linear("ne,ed->nd", vp, pj["w2"], quant)
        x = jnp.concatenate([vp, x], 0)[: ids.shape[0]]
    x, _ = jax.lax.scan(partial(_layer, m, quant), x, params["layers"])
    return _rms(x, params["final_norm"]["scale"], m["rms_norm_eps"])


@partial(jax.jit, static_argnums=(0,))
def _head(quant, emb, h, rows):
    """Logits [n, V] of hidden rows ``h[rows]`` (tied unembedding)."""
    return _linear("nd,vd->nv", h[rows], emb, quant)


def _mkey(m: Dict):
    """The sizes the reference's programs are specialised on. Rotary
    scaling other than M-RoPE (which on 1-D positions is plain rotary)
    is not implemented, and refused."""
    scaling = m.get("rope_scaling") or {}
    if scaling and scaling.get("type") != "mrope":
        raise ValueError(f"rope_scaling {scaling.get('type')!r} is not in "
                         "the reference")
    keep = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    return tuple((k, m[k]) for k in keep) + (
        ("partial_rotary_factor", m.get("partial_rotary_factor", 1.0)),)


def select_visual(raw: np.ndarray, keep: Optional[int]) -> np.ndarray:
    """The visual rows that enter the backbone. ``keep`` rows of lowest
    L2 norm, in their original order -- the salience the program's
    FastV strategy uses in place of attention -- or all rows."""
    if keep is None or keep >= len(raw):
        return raw
    norms = np.linalg.norm(raw.astype(np.float64), axis=-1)
    return raw[np.sort(np.argsort(norms, kind="stable")[:keep])]


def served_logits(m: Dict, params, tokens: Sequence[int],
                  visual: Optional[np.ndarray], served: Sequence[int],
                  pad_len: int, max_served: int,
                  quant: str = "none") -> jax.Array:
    """Logits [max_served, V] whose first ``len(served)`` rows are at the
    positions that predicted a served token: the last prompt position,
    then each served token but the last. ``visual`` is already selected
    (``select_visual``). Fixed shapes: one program per cell."""
    nv = 0 if visual is None else len(visual)
    seq = list(tokens) + list(served[:-1])
    if nv + len(seq) > pad_len:
        raise ValueError(f"{nv + len(seq)} positions > pad {pad_len}")
    ids = np.zeros(pad_len, np.int32)
    ids[: len(seq)] = seq
    vis = (np.zeros((0, m["hidden_size"]), np.float32) if visual is None
           else np.asarray(visual, np.float32))
    h = _hidden(_mkey(m), quant, params, jnp.asarray(ids), jnp.asarray(vis))
    rows = np.zeros(max_served, np.int32)
    rows[: len(served)] = nv + len(tokens) - 1 + np.arange(len(served))
    return _head(quant, params["embed"]["tok"], h, jnp.asarray(rows))
