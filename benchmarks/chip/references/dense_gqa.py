"""The dense decoder: grouped-query attention, SwiGLU MLP, RMSNorm.

A reference module holds everything in the benchmark that knows one
family's layer equations. A configuration file names its module with
``"reference": "<name>"`` (``references/<name>.py``, found by name as
``metrics/<name>.py`` is); without the key it is this one. A module
gives, for ``m`` the configuration's ``model`` object (published key
names, with ``departures.served`` in place where the harness says so):

- ``program_fields(m)``: the program's ``ModelConfig`` fields, refusing a
  model the program cannot serve;
- ``layout(m)``: the weights as a ``weights.Layout``, the nested dict the
  program's ``init`` returns; ``weights.make`` draws them;
- ``served_logits(m, params, tokens, visual, served, pad_len, max_served,
  quant)``: float32 reference logits at the positions that predicted the
  served tokens, and with ``quant="fp8"`` the control's;
- ``prefill_cost``, ``decode_cost``, ``window_ops`` and ``least_time``:
  the work counts ``metrics/*.py`` read as ``ctx.flops``.

The counts must be a lower bound on the work the result needs, whatever
program computes it. Then no share of a roofline or of a peak that they
feed can pass 100%, and a program that removes waste raises its share.
A count that depends on what the program decides as it runs (which
experts a token is routed to) is not of this interface: a module adds it
through a reader of new counters.

The reference imports nothing of the program. It follows the published
layer equations of a pre-norm decoder (RMSNorm, grouped-query attention
with rotary positions, SwiGLU MLP, tied unembedding; for a
vision-language model, visual embeddings through a two-layer GELU
projector placed before the text), computed in float32 with every matrix
product at ``Precision.HIGHEST``. Where the program departs from the
published model, the reference takes the program's inputs, and the
configuration file's ``departures`` names each case (visual tokens on
1-D positions, no attention biases, the projector's tanh GELU, the L2
salience that stands in for FastV's attention). Where the program serves
another value of a published key (the norm epsilon, the share of the
head that rotates), ``departures.served`` gives it and ``m`` carries it
(``harness.served_model``). The whole sequence (prompt and served
tokens) runs at once, layer by layer under ``lax.scan``, padded to a
fixed length so one program serves every request of a cell.

The counts take the work the result needs, not the work the program
happens to do: a prefill unembeds only its last position, a decode step
reads the key/value entries of its live positions only, and padding,
empty slots and full-sequence logits count for nothing. Weights are
counted at ``flops.BYTES_PER_PARAM`` (bfloat16, as served).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.flops import BYTES_PER_PARAM, KV_BYTES, least_time
from benchmarks.chip.reference import HIGHEST, linear, rms, rope
from benchmarks.chip.weights import Layout

# published key -> ModelConfig field, for the keys the program takes
PROGRAM_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype", "visual_tokens": "num_visual_tokens",
}


def program_fields(m: Dict) -> Dict:
    """The program's ``ModelConfig`` fields for every size ``m`` states."""
    over = {PROGRAM_KEYS[k]: v for k, v in m.items() if k in PROGRAM_KEYS}
    scaling = m.get("rope_scaling") or {}
    if scaling.get("type") == "mrope":
        over["mrope_sections"] = tuple(scaling["mrope_section"])
    if m.get("hidden_act", "silu") != "silu":
        raise ValueError("only SwiGLU (silu) MLPs are served")
    return over


# ------------------------------------------------------------- weights --
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


# ----------------------------------------------------------- reference --
def _layer(m, quant, x, lp):
    s = x.shape[0]
    h, k, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    eps = m["rms_norm_eps"]
    pos = jnp.arange(s)
    a = rms(x, lp["ln1"]["scale"], eps)
    q = linear("sd,dhe->she", a, lp["attn"]["wq"], quant)
    kk = linear("sd,dke->ske", a, lp["attn"]["wk"], quant)
    v = linear("sd,dke->ske", a, lp["attn"]["wv"], quant)
    frac = m["partial_rotary_factor"]
    q = rope(q, pos, m["rope_theta"], frac)
    kk = rope(kk, pos, m["rope_theta"], frac)
    q = q.reshape(s, k, h // k, hd)
    scores = jnp.einsum("qkgd,ckd->kgqc", q, kk, precision=HIGHEST)
    scores = scores / np.sqrt(hd)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("kgqc,ckd->qkgd", p, v, precision=HIGHEST)
    o = o.reshape(s, h, hd)
    x = x + linear("she,hed->sd", o, lp["attn"]["wo"], quant)
    b = rms(x, lp["ln2"]["scale"], eps)
    g = linear("sd,df->sf", b, lp["mlp"]["wi_gate"], quant)
    u = linear("sd,df->sf", b, lp["mlp"]["wi_up"], quant)
    x = x + linear("sf,fd->sd", jax.nn.silu(g) * u, lp["mlp"]["wo"], quant)
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
        vp = jax.nn.gelu(linear("nd,de->ne", visual, pj["w1"], quant),
                         approximate=True)
        vp = linear("ne,ed->nd", vp, pj["w2"], quant)
        x = jnp.concatenate([vp, x], 0)[: ids.shape[0]]
    x, _ = jax.lax.scan(partial(_layer, m, quant), x, params["layers"])
    return rms(x, params["final_norm"]["scale"], m["rms_norm_eps"])


@partial(jax.jit, static_argnums=(0,))
def _head(quant, emb, h, rows):
    """Logits [n, V] of hidden rows ``h[rows]`` (tied unembedding)."""
    return linear("nd,vd->nv", h[rows], emb, quant)


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


def served_logits(m: Dict, params, tokens: Sequence[int],
                  visual: Optional[np.ndarray], served: Sequence[int],
                  pad_len: int, max_served: int,
                  quant: str = "none") -> jax.Array:
    """Logits [max_served, V] whose first ``len(served)`` rows are at the
    positions that predicted a served token: the last prompt position,
    then each served token but the last. ``visual`` is already selected
    (``reference.select_visual``). Fixed shapes: one program per cell."""
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


# -------------------------------------------------------------- counts --
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
