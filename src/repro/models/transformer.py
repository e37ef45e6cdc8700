"""Model assembly for all six architecture families.

Production path scans over layers with *stacked* params (MaxText-style):
each homogeneous segment of the network is one ``lax.scan`` whose xs are the
stacked layer params (and the stacked per-layer cache for prefill; decode
carries the stack and writes its new rows in place).
This keeps HLO size O(1) in depth for the 88--96 layer archs.

An unscanned *introspection* path (``scan=False``) runs a Python loop and
returns per-layer attention statistics -- this is what the survey's
attention-score-driven techniques (FastV, SnapKV, H2O, PyramidKV) consume;
it is used by the serving engine and benchmarks on small models only.

Entry points (uniform across families):
  forward(params, batch)                 -> logits [B,S,V] (+aux)
  prefill(params, batch, cache_len, windowed) -> (logits [B,S,V], cache)
  decode_step(params, cache, tokens, pos)     -> (logits [B,V], cache)
  param_specs() / cache_specs(batch, cache_len, windowed)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import rwkv as R
from repro.models.layers import ParamSpec, spec


# --------------------------------------------------------------------------
# spec-tree utilities
# --------------------------------------------------------------------------

def stack_specs(tree, n: int, axis_name: Optional[str] = "layers"):
    """Prepend a stacked-layer dim to every ParamSpec in a tree."""
    def _one(path, s: ParamSpec):
        return ParamSpec((n,) + s.shape, (axis_name,) + s.axes, s.init,
                         s.scale, s.dtype)
    return L.tree_map_specs(_one, tree)


def specs_to_struct(tree, default_dtype):
    return L.abstract_params(tree, default_dtype)


def _ckpt(fn, remat):
    """remat: False | True ('full': save nothing) | 'dots' (save matmul
    outputs -- the backward pass reuses them instead of re-running the
    forward, halving fsdp weight re-gather traffic at the cost of stored
    activations; §Perf iteration 3)."""
    if not remat:
        return fn
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _zeros_from_specs(tree, default_dtype):
    def _one(path, s: ParamSpec):
        dt = jnp.dtype(s.dtype or default_dtype)
        arr = jnp.zeros(s.shape, dt)
        if path and path[-1] == "slot_pos":
            arr = arr - 1
        return arr
    return L.tree_map_specs(_one, tree)


# --------------------------------------------------------------------------
# per-family layer bodies
# --------------------------------------------------------------------------

def _dense_layer_specs(cfg: ModelConfig) -> Dict[str, Any]:
    out = {
        "ln1": L.norm_specs(cfg),
        "attn": attn.attn_specs(cfg),
        "ln2": L.norm_specs(cfg),
    }
    if cfg.num_experts:
        out["moe"] = MOE.moe_specs(cfg)
    else:
        out["mlp"] = L.mlp_specs(cfg)
    return out


def _dense_layer_fwd(cfg, p, x, cos, sin, *, positions, window, causal=True,
                     moe_cap=1.25):
    """Full-seq layer (train/prefill without cache)."""
    h = L.apply_norm(p["ln1"], x, cfg.norm)
    if cfg.use_mla:
        a = attn.mla_full_attention(p["attn"], h, cos, sin, cfg,
                                    window=window, positions=positions)
    else:
        a = attn.full_attention(p["attn"], h, cos, sin, cfg, causal=causal,
                                window=window, positions=positions)
    x = x + a
    h = L.apply_norm(p["ln2"], x, cfg.norm)
    aux = {}
    if cfg.num_experts and "moe" in p:
        f, aux = MOE.apply_moe(p["moe"], h, cfg, capacity_factor=moe_cap)
    else:
        f = L.apply_mlp(p["mlp"], h, cfg.activation)
    return x + f, aux


def _dense_layer_prefill(cfg, p, x, cos, sin, cache, *, positions, window,
                         moe_cap=1.25):
    h = L.apply_norm(p["ln1"], x, cfg.norm)
    if cfg.use_mla:
        a, cache = attn.mla_full_attention(p["attn"], h, cos, sin, cfg,
                                           window=window, positions=positions,
                                           cache=cache)
    else:
        a, cache = attn.prefill_into_cache(p["attn"], h, cos, sin, cfg, cache,
                                           window=window, positions=positions)
    x = x + a
    h = L.apply_norm(p["ln2"], x, cfg.norm)
    if cfg.num_experts and "moe" in p:
        f, _ = MOE.apply_moe(p["moe"], h, cfg, capacity_factor=moe_cap)
    else:
        f = L.apply_mlp(p["mlp"], h, cfg.activation)
    return x + f, cache


def _dense_layer_decode(cfg, p, x, cos, sin, cache, pos, *, window,
                        moe_cap=None, weight_stationary=False, layer=None,
                        lens=None):
    """One layer's decode. ``cache`` is that layer's, or with ``layer`` the
    stacked cache, whose layer ``layer`` gets the new token in place.
    ``lens``: positions each row attends (``decode_attention``)."""
    if weight_stationary:
        x = L.constrain_replicated(x)
    h = L.apply_norm(p["ln1"], x, cfg.norm)
    if cfg.use_mla:
        a, cache = attn.mla_decode_attention(p["attn"], h, cos, sin, cfg,
                                             cache, pos, window=window,
                                             layer=layer)
    else:
        a, cache = attn.decode_attention(p["attn"], h, cos, sin, cfg, cache,
                                         pos, window=window, layer=layer,
                                         lens=lens)
    x = x + a
    h = L.apply_norm(p["ln2"], x, cfg.norm)
    if cfg.num_experts and "moe" in p:
        f, _ = MOE.apply_moe(p["moe"], h, cfg, capacity_factor=moe_cap)
    else:
        f = L.apply_mlp(p["mlp"], h, cfg.activation)
    return x + f, cache


# --------------------------------------------------------------------------
# Model
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------- specs --
    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        out: Dict[str, Any] = {"embed": L.embed_specs(cfg),
                               "final_norm": L.norm_specs(cfg)}
        if cfg.family in ("dense", "vlm"):
            out["layers"] = stack_specs(_dense_layer_specs(cfg), cfg.num_layers)
            if cfg.family == "vlm":
                if cfg.projector == "perceiver":
                    from repro.models.resampler import resampler_specs
                    out["projector"] = resampler_specs(
                        cfg, num_latents=cfg.num_latents)
                else:
                    out["projector"] = {
                        "w1": spec((cfg.d_model, cfg.d_model),
                                   ("embed", "embed_out")),
                        "w2": spec((cfg.d_model, cfg.d_model),
                                   ("embed_out", "embed")),
                    }
        elif cfg.family == "moe":
            kd = cfg.first_k_dense_layers
            if kd:
                dense_cfg = cfg.with_(num_experts=0)
                out["dense_layers"] = stack_specs(
                    _dense_layer_specs(dense_cfg), kd)
            out["layers"] = stack_specs(_dense_layer_specs(cfg),
                                        cfg.num_layers - kd)
        elif cfg.family == "ssm":
            out["layers"] = stack_specs(
                {"ln1": L.norm_specs(cfg), "ln2": L.norm_specs(cfg),
                 **R.rwkv_specs(cfg)}, cfg.num_layers)
        elif cfg.family == "hybrid":
            out["layers"] = stack_specs(
                {"ln": L.norm_specs(cfg), "mamba": M.mamba_specs(cfg)},
                cfg.num_layers)
            out["shared_attn"] = {
                "ln": L.norm_specs(cfg),
                "attn": attn.attn_specs(cfg),
                "ln2": L.norm_specs(cfg),
                "mlp": L.mlp_specs(cfg),
            }
        elif cfg.family == "audio":
            enc_cfg = cfg
            out["encoder"] = {
                "layers": stack_specs(_dense_layer_specs(enc_cfg),
                                      cfg.encoder_layers),
                "norm": L.norm_specs(cfg),
                "pos_embed": spec((cfg.encoder_seq, cfg.d_model),
                                  (None, "embed"), scale=0.02),
            }
            out["layers"] = stack_specs(
                {"ln1": L.norm_specs(cfg), "attn": attn.attn_specs(cfg),
                 "ln_x": L.norm_specs(cfg), "xattn": attn.cross_attn_specs(cfg),
                 "ln2": L.norm_specs(cfg), "mlp": L.mlp_specs(cfg)},
                cfg.num_layers)
        else:
            raise ValueError(cfg.family)
        return out

    def init(self, key) -> Dict[str, Any]:
        return L.init_params(self.param_specs(), key, self.cfg.dtype)

    def abstract_params(self):
        return L.abstract_params(self.param_specs(), self.cfg.dtype)

    # ------------------------------------------------------------- cache --
    def n_hybrid_groups(self) -> Tuple[int, int]:
        cfg = self.cfg
        g = cfg.num_layers // cfg.attn_layer_period
        rem = cfg.num_layers - g * cfg.attn_layer_period
        return g, rem

    def cache_specs(self, batch: int, cache_len: int,
                    windowed: bool = False) -> Dict[str, Any]:
        cfg = self.cfg
        if cfg.family in ("dense", "vlm"):
            return {"layers": stack_specs(
                attn.kv_cache_specs(cfg, batch, cache_len, windowed),
                cfg.num_layers)}
        if cfg.family == "moe":
            kd = cfg.first_k_dense_layers
            out = {"layers": stack_specs(
                attn.kv_cache_specs(cfg, batch, cache_len, windowed),
                cfg.num_layers - kd)}
            if kd:
                out["dense_layers"] = stack_specs(
                    attn.kv_cache_specs(cfg, batch, cache_len, windowed), kd)
            return out
        if cfg.family == "ssm":
            return {"layers": stack_specs(R.rwkv_cache_specs(cfg, batch),
                                          cfg.num_layers)}
        if cfg.family == "hybrid":
            g, _ = self.n_hybrid_groups()
            return {
                "layers": stack_specs(M.mamba_cache_specs(cfg, batch),
                                      cfg.num_layers),
                # shared attn block: one (windowed) KV cache per invocation
                "shared_attn": stack_specs(
                    attn.kv_cache_specs(cfg, batch, cache_len, windowed=True),
                    g),
            }
        if cfg.family == "audio":
            return {
                "layers": stack_specs(
                    attn.kv_cache_specs(cfg, batch, cache_len, windowed),
                    cfg.num_layers),
                "cross": stack_specs(
                    {"k": spec((batch, cfg.encoder_seq, cfg.num_kv_heads,
                                cfg.head_dim),
                               ("batch", "enc_seq", "kv_heads", None),
                               init="zeros"),
                     "v": spec((batch, cfg.encoder_seq, cfg.num_kv_heads,
                                cfg.head_dim),
                               ("batch", "enc_seq", "kv_heads", None),
                               init="zeros")},
                    cfg.num_layers),
            }
        raise ValueError(cfg.family)

    def init_cache(self, batch, cache_len, windowed=False):
        return _zeros_from_specs(self.cache_specs(batch, cache_len, windowed),
                                 self.cfg.dtype)

    # ------------------------------------------------------- rope helpers --
    def _cos_sin(self, batch, positions):
        """positions: [S] or [B,S] text pos, or [3,B,S] for M-RoPE."""
        cfg = self.cfg
        if cfg.is_attention_free:
            return None, None
        hd = cfg.qk_rope_head_dim if cfg.use_mla else cfg.head_dim
        if cfg.use_mrope:
            if positions.ndim == 2:     # text-only fallback: t=h=w
                positions = jnp.broadcast_to(positions[None],
                                             (3,) + positions.shape)
            return L.mrope_cos_sin(positions, hd, cfg.rope_theta,
                                   cfg.mrope_sections)
        return L.rope_cos_sin(positions, hd, cfg.rope_theta)

    # ------------------------------------------------------------ embed --
    def _embed_inputs(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        """Returns (x [B,S,d], positions [B,S] or [3,B,S])."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = L.embed_tokens(params["embed"], tokens)
        if cfg.family == "vlm" and "visual_embeds" in batch:
            ve = batch["visual_embeds"].astype(x.dtype)
            if cfg.projector == "perceiver":
                # Flamingo resampler: any number of patches -> num_latents
                # fixed visual tokens (survey dim 3a)
                from repro.models.resampler import apply_resampler
                ve = apply_resampler(params["projector"], ve)
            else:
                w1, w2 = params["projector"]["w1"], params["projector"]["w2"]
                ve = jax.nn.gelu(
                    jnp.einsum("bnd,de->bne", ve, w1,
                               preferred_element_type=jnp.float32)
                ).astype(x.dtype)
                ve = jnp.einsum("bne,ed->bnd", ve, w2,
                                preferred_element_type=jnp.float32
                                ).astype(x.dtype)
            x = jnp.concatenate([ve, x], axis=1)
        b, s = x.shape[0], x.shape[1]
        if "positions" in batch:
            positions = batch["positions"]
        else:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                         (b, s))
        return x, positions

    # ----------------------------------------------------------- forward --
    def forward(self, params, batch, *, window: Optional[int] = None,
                remat: bool = False,
                moe_cap: Optional[float] = 1.25) -> Tuple[jax.Array, Dict]:
        """Full-sequence logits (training / scoring). Scanned over layers."""
        cfg = self.cfg
        window = 0 if window is None else window
        if cfg.family == "audio":
            return self._forward_audio(params, batch, remat=remat)
        x, positions = self._embed_inputs(params, batch)
        cos, sin = self._cos_sin(x.shape[0], positions)
        pos_1d = positions[0, 0] if positions.ndim == 3 else positions[0]

        aux_acc = {}
        if cfg.family in ("dense", "vlm", "moe"):
            def body(carry, lp):
                x = carry
                x, aux = _dense_layer_fwd(cfg, lp, x, cos, sin,
                                          positions=pos_1d, window=window,
                                          moe_cap=moe_cap)
                return x, aux.get("lb_loss", jnp.zeros((), jnp.float32))
            if cfg.family == "moe" and cfg.first_k_dense_layers:
                dense_cfg = cfg.with_(num_experts=0)

                def dbody(carry, lp):
                    x, _ = _dense_layer_fwd(dense_cfg, lp, carry, cos, sin,
                                            positions=pos_1d, window=window)
                    return x, None
                x, _ = jax.lax.scan(_ckpt(dbody, remat),
                                    x, params["dense_layers"])
            x, lb = jax.lax.scan(_ckpt(body, remat),
                                 x, params["layers"])
            if cfg.num_experts:
                aux_acc["lb_loss"] = jnp.mean(lb)
        elif cfg.family == "ssm":
            def body(carry, lp):
                x = carry
                h = L.apply_norm(lp["ln1"], x, cfg.norm)
                tm, _ = R.time_mix_forward(lp["time_mix"], h, cfg)
                x = x + tm
                h = L.apply_norm(lp["ln2"], x, cfg.norm)
                cm, _ = R.channel_mix_forward(lp["channel_mix"], h, cfg)
                return x + cm, None
            x, _ = jax.lax.scan(_ckpt(body, remat),
                                x, params["layers"])
        elif cfg.family == "hybrid":
            x = self._hybrid_forward(params, x, cos, sin, pos_1d, remat)
        else:
            raise ValueError(cfg.family)

        x = L.apply_norm(params["final_norm"], x, cfg.norm)
        logits = L.unembed(params["embed"], x, cfg.logits_softcap)
        return logits, aux_acc

    def _hybrid_forward(self, params, x, cos, sin, pos_1d, remat):
        cfg = self.cfg
        period = cfg.attn_layer_period
        g, rem = self.n_hybrid_groups()
        sp = params["shared_attn"]

        def mamba_body(carry, lp):
            h = L.apply_norm(lp["ln"], carry, cfg.norm)
            y, _ = M.mamba_forward(lp["mamba"], h, cfg, chunk=self._chunk(h))
            return carry + y, None

        def shared_block(x):
            h = L.apply_norm(sp["ln"], x, cfg.norm)
            a = attn.full_attention(sp["attn"], h, cos, sin, cfg, causal=True,
                                    window=cfg.sliding_window,
                                    positions=pos_1d)
            x = x + a
            h = L.apply_norm(sp["ln2"], x, cfg.norm)
            return x + L.apply_mlp(sp["mlp"], h, cfg.activation)

        stacked = params["layers"]
        main = jax.tree.map(lambda a: a[:g * period].reshape(
            (g, period) + a.shape[1:]), stacked)
        tail = jax.tree.map(lambda a: a[g * period:], stacked)

        def group_body(carry, gp):
            x, _ = jax.lax.scan(mamba_body, carry, gp)
            return shared_block(x), None
        x, _ = jax.lax.scan(_ckpt(group_body, remat),
                            x, main)
        if rem:
            x, _ = jax.lax.scan(mamba_body, x, tail)
        return x

    def _chunk(self, x):
        t = x.shape[1]
        for c in (128, 64, 32, 16, 8, 4, 2, 1):
            if t % c == 0:
                return c
        return 1

    def _forward_audio(self, params, batch, remat=False):
        cfg = self.cfg
        frames = batch["frames"].astype(jnp.dtype(cfg.dtype))
        enc = frames + params["encoder"]["pos_embed"][None, :frames.shape[1]]

        def enc_body(carry, lp):
            x, _ = _dense_layer_fwd(cfg, lp, carry, None, None,
                                    positions=jnp.arange(carry.shape[1]),
                                    window=0, causal=False)
            return x, None
        enc, _ = jax.lax.scan(enc_body, enc, params["encoder"]["layers"])
        enc = L.apply_norm(params["encoder"]["norm"], enc, cfg.norm)

        tokens = batch["tokens"]
        x = L.embed_tokens(params["embed"], tokens)
        s = x.shape[1]
        pos = jnp.arange(s, dtype=jnp.int32)
        cos, sin = L.rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)
        enc_pos = jnp.arange(enc.shape[1], dtype=jnp.int32)

        def dec_body(carry, lp):
            x = carry
            h = L.apply_norm(lp["ln1"], x, cfg.norm)
            a = attn.full_attention(lp["attn"], h, cos, sin, cfg, causal=True,
                                    positions=pos)
            x = x + a
            # cross attention
            h = L.apply_norm(lp["ln_x"], x, cfg.norm)
            q = jnp.einsum("bsd,dhe->bshe", h, lp["xattn"]["wq"],
                           preferred_element_type=jnp.float32).astype(x.dtype)
            k = jnp.einsum("bsd,dke->bske", enc, lp["xattn"]["wk"],
                           preferred_element_type=jnp.float32).astype(x.dtype)
            v = jnp.einsum("bsd,dke->bske", enc, lp["xattn"]["wv"],
                           preferred_element_type=jnp.float32).astype(x.dtype)
            qg = q.reshape(q.shape[0], q.shape[1], cfg.num_kv_heads,
                           cfg.num_heads // cfg.num_kv_heads, cfg.head_dim)
            o = attn.blockwise_sdpa(qg, k, v, q_pos=pos, k_pos=enc_pos,
                                    causal=False)
            x = x + attn.out_proj(lp["xattn"], o)
            h = L.apply_norm(lp["ln2"], x, cfg.norm)
            return x + L.apply_mlp(lp["mlp"], h, cfg.activation), None

        x, _ = jax.lax.scan(_ckpt(dec_body, remat),
                            x, params["layers"])
        x = L.apply_norm(params["final_norm"], x, cfg.norm)
        return L.unembed(params["embed"], x, cfg.logits_softcap), {}

    # -------------------------------------------------------------- loss --
    def loss(self, params, batch, *, remat: bool = True):
        cfg = self.cfg
        logits, aux = self.forward(params, batch, remat=remat)
        labels = batch.get("labels", None)
        tokens = batch["tokens"]
        if labels is None:
            labels = jnp.concatenate(
                [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        # VLM: logits cover [visual | text]; loss only on text positions
        if logits.shape[1] != labels.shape[1]:
            logits = logits[:, logits.shape[1] - labels.shape[1]:]
        mask = batch.get("loss_mask",
                         jnp.ones(labels.shape, jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = (lse - ll) * mask
        loss = nll.sum() / jnp.maximum(mask.sum(), 1.0)
        if "lb_loss" in aux:
            loss = loss + self.cfg.router_aux_loss_coef * aux["lb_loss"]
        return loss, {"nll": loss, **{k: v for k, v in aux.items()
                                      if v.ndim == 0}}

    # ----------------------------------------------------------- prefill --
    def prefill(self, params, batch, *, cache_len: Optional[int] = None,
                windowed: bool = False, window: Optional[int] = None,
                moe_cap: Optional[float] = 1.25, last_only: bool = False):
        """Run the full prompt, returning (logits, filled cache).

        ``last_only``: unembed only the final position (logits [B,1,V]) --
        what a serving prefill actually needs; avoids materializing the
        [B,S,V] logits tensor (0.5 TB/device at 32k prefill x 32k vocab).
        """
        cfg = self.cfg
        window = (cfg.sliding_window if windowed else 0) if window is None \
            else window
        if cfg.family == "audio":
            return self._prefill_audio(params, batch, cache_len,
                                       last_only=last_only)
        x, positions = self._embed_inputs(params, batch)
        b, s = x.shape[0], x.shape[1]
        # cache must cover the full (visual + text) prefill length
        cache_len = max(cache_len or 0, s)
        cache = self.init_cache(b, cache_len, windowed)
        cos, sin = self._cos_sin(b, positions)
        pos_1d = positions[0, 0] if positions.ndim == 3 else positions[0]

        if cfg.family in ("dense", "vlm", "moe"):
            def body(carry, xs):
                lp, lcache = xs
                x, lcache = _dense_layer_prefill(cfg, lp, carry, cos, sin,
                                                 lcache, positions=pos_1d,
                                                 window=window,
                                                 moe_cap=moe_cap)
                return x, lcache
            if cfg.family == "moe" and cfg.first_k_dense_layers:
                dense_cfg = cfg.with_(num_experts=0)

                def dbody(carry, xs):
                    lp, lcache = xs
                    x, lcache = _dense_layer_prefill(
                        dense_cfg, lp, carry, cos, sin, lcache,
                        positions=pos_1d, window=window)
                    return x, lcache
                x, dcache = jax.lax.scan(
                    dbody, x, (params["dense_layers"], cache["dense_layers"]))
                cache["dense_layers"] = dcache
            x, lcache = jax.lax.scan(body, x,
                                     (params["layers"], cache["layers"]))
            cache["layers"] = lcache
        elif cfg.family == "ssm":
            def body(carry, xs):
                lp, st = xs
                x = carry
                h = L.apply_norm(lp["ln1"], x, cfg.norm)
                tm, tm_state = R.time_mix_forward(lp["time_mix"], h, cfg)
                x = x + tm
                h = L.apply_norm(lp["ln2"], x, cfg.norm)
                cm, cm_state = R.channel_mix_forward(lp["channel_mix"], h, cfg)
                new_state = {"tm_shift": tm_state["tm_shift"],
                             "wkv": tm_state["wkv"],
                             "cm_shift": cm_state["cm_shift"]}
                return x + cm, new_state
            x, states = jax.lax.scan(body, x,
                                     (params["layers"], cache["layers"]))
            cache["layers"] = states
        elif cfg.family == "hybrid":
            x, cache = self._hybrid_prefill(params, x, cos, sin, pos_1d, cache)
        else:
            raise ValueError(cfg.family)

        if last_only:
            x = x[:, -1:]
        x = L.apply_norm(params["final_norm"], x, cfg.norm)
        logits = L.unembed(params["embed"], x, cfg.logits_softcap)
        return logits, cache

    def _hybrid_prefill(self, params, x, cos, sin, pos_1d, cache):
        cfg = self.cfg
        period = cfg.attn_layer_period
        g, rem = self.n_hybrid_groups()
        sp = params["shared_attn"]

        def mamba_body(carry, xs):
            lp, st = xs
            h = L.apply_norm(lp["ln"], carry, cfg.norm)
            y, st = M.mamba_forward(lp["mamba"], h, cfg,
                                    chunk=self._chunk(h), cache=st)
            return carry + y, st

        stacked, mstate = params["layers"], cache["layers"]
        main_p = jax.tree.map(lambda a: a[:g * period].reshape(
            (g, period) + a.shape[1:]), stacked)
        main_s = jax.tree.map(lambda a: a[:g * period].reshape(
            (g, period) + a.shape[1:]), mstate)
        tail_p = jax.tree.map(lambda a: a[g * period:], stacked)
        tail_s = jax.tree.map(lambda a: a[g * period:], mstate)

        def group_body(carry, xs):
            gp, gs, acache = xs
            x, gs = jax.lax.scan(mamba_body, carry, (gp, gs))
            h = L.apply_norm(sp["ln"], x, cfg.norm)
            a, acache = attn.prefill_into_cache(
                sp["attn"], h, cos, sin, cfg, acache,
                window=cfg.sliding_window, positions=pos_1d)
            x = x + a
            h = L.apply_norm(sp["ln2"], x, cfg.norm)
            x = x + L.apply_mlp(sp["mlp"], h, cfg.activation)
            return x, (gs, acache)

        x, (main_s_new, acaches) = jax.lax.scan(
            group_body, x, (main_p, main_s, cache["shared_attn"]))
        if rem:
            x, tail_s_new = jax.lax.scan(mamba_body, x, (tail_p, tail_s))
        else:
            tail_s_new = tail_s
        new_mstate = jax.tree.map(
            lambda a, b: jnp.concatenate(
                [a.reshape((g * period,) + a.shape[2:]), b], axis=0),
            main_s_new, tail_s_new)
        cache = dict(cache, layers=new_mstate, shared_attn=acaches)
        return x, cache

    def _prefill_audio(self, params, batch, cache_len, last_only=False):
        cfg = self.cfg
        frames = batch["frames"].astype(jnp.dtype(cfg.dtype))
        enc = frames + params["encoder"]["pos_embed"][None, :frames.shape[1]]

        def enc_body(carry, lp):
            x, _ = _dense_layer_fwd(cfg, lp, carry, None, None,
                                    positions=jnp.arange(carry.shape[1]),
                                    window=0, causal=False)
            return x, None
        enc, _ = jax.lax.scan(enc_body, enc, params["encoder"]["layers"])
        enc = L.apply_norm(params["encoder"]["norm"], enc, cfg.norm)

        tokens = batch["tokens"]
        b, s = tokens.shape
        cache_len = cache_len or s
        cache = self.init_cache(b, cache_len)
        x = L.embed_tokens(params["embed"], tokens)
        pos = jnp.arange(s, dtype=jnp.int32)
        cos, sin = L.rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)
        enc_pos = jnp.arange(enc.shape[1], dtype=jnp.int32)

        def dec_body(carry, xs):
            lp, lcache, xkv = xs
            x = carry
            h = L.apply_norm(lp["ln1"], x, cfg.norm)
            a, lcache = attn.prefill_into_cache(lp["attn"], h, cos, sin, cfg,
                                                lcache, positions=pos)
            x = x + a
            h = L.apply_norm(lp["ln_x"], x, cfg.norm)
            xk = jnp.einsum("bsd,dke->bske", enc, lp["xattn"]["wk"],
                            preferred_element_type=jnp.float32).astype(x.dtype)
            xv = jnp.einsum("bsd,dke->bske", enc, lp["xattn"]["wv"],
                            preferred_element_type=jnp.float32).astype(x.dtype)
            xkv = {"k": xk, "v": xv}
            q = jnp.einsum("bsd,dhe->bshe", h, lp["xattn"]["wq"],
                           preferred_element_type=jnp.float32).astype(x.dtype)
            qg = q.reshape(b, s, cfg.num_kv_heads,
                           cfg.num_heads // cfg.num_kv_heads, cfg.head_dim)
            o = attn.blockwise_sdpa(qg, xk, xv, q_pos=pos, k_pos=enc_pos,
                                    causal=False)
            x = x + attn.out_proj(lp["xattn"], o)
            h = L.apply_norm(lp["ln2"], x, cfg.norm)
            return x + L.apply_mlp(lp["mlp"], h, cfg.activation), (lcache, xkv)

        x, (lcaches, xkvs) = jax.lax.scan(
            dec_body, x, (params["layers"], cache["layers"], cache["cross"]))
        cache = dict(cache, layers=lcaches, cross=xkvs)
        if last_only:
            x = x[:, -1:]
        x = L.apply_norm(params["final_norm"], x, cfg.norm)
        return L.unembed(params["embed"], x, cfg.logits_softcap), cache

    # ------------------------------------------------------------ extend --
    def extend(self, params, cache, tokens, start, *,
               window: Optional[int] = None,
               moe_cap: Optional[float] = 1.25):
        """Chunked continuation: score ``tokens [B,S_new]`` appended to an
        existing cache at offset ``start`` -- a scalar (whole batch extends
        from the same position) or [B] per-request offsets (each row's
        block lands at its own cache position).

        Powers Sarathi-style chunked prefill, RadixAttention prefix reuse
        (skip the cached prefix, extend with the suffix), and speculative-
        decoding verification (score the draft block in one pass; the [B]
        form is the engine's batched multi-slot verify).
        Supported for attention-cache families (dense / vlm / moe / audio
        self-attn); SSM/hybrid prefill is already O(1)-state streaming.
        """
        cfg = self.cfg
        if cfg.family not in ("dense", "vlm", "moe"):
            raise NotImplementedError(
                f"extend() not supported for family {cfg.family!r}")
        window = (window or 0)
        x = L.embed_tokens(params["embed"], tokens)
        b, s_new = tokens.shape
        positions = jnp.broadcast_to(
            attn._extend_positions(start, s_new), (b, s_new))
        cos, sin = self._cos_sin(b, positions)

        def make_body(lcfg):
            def body(carry, xs):
                lp, lcache = xs
                x = carry
                h = L.apply_norm(lp["ln1"], x, cfg.norm)
                if lcfg.use_mla:
                    a, lcache = attn.mla_append_attention(
                        lp["attn"], h, cos, sin, lcfg, lcache, start,
                        window=window)
                else:
                    a, lcache = attn.append_attention(
                        lp["attn"], h, cos, sin, lcfg, lcache, start,
                        window=window)
                x = x + a
                h = L.apply_norm(lp["ln2"], x, cfg.norm)
                if lcfg.num_experts and "moe" in lp:
                    f, _ = MOE.apply_moe(lp["moe"], h, lcfg,
                                         capacity_factor=moe_cap)
                else:
                    f = L.apply_mlp(lp["mlp"], h, lcfg.activation)
                return x + f, lcache
            return body

        if cfg.family == "moe" and cfg.first_k_dense_layers:
            dense_cfg = cfg.with_(num_experts=0)
            x, dcache = jax.lax.scan(
                make_body(dense_cfg), x,
                (params["dense_layers"], cache["dense_layers"]))
            cache = dict(cache, dense_layers=dcache)
        x, lcache = jax.lax.scan(make_body(cfg), x,
                                 (params["layers"], cache["layers"]))
        cache = dict(cache, layers=lcache)
        x = L.apply_norm(params["final_norm"], x, cfg.norm)
        logits = L.unembed(params["embed"], x, cfg.logits_softcap)
        return logits, cache

    # ------------------------------------------------------------ decode --
    def decode_step(self, params, cache, tokens, pos, *,
                    windowed: bool = False, window: Optional[int] = None,
                    moe_cap: Optional[float] = None,
                    weight_stationary: bool = False, lens=None):
        """tokens [B,1] -> (logits [B,V], new cache).

        pos: scalar int32 (all requests at the same position -- dry-run)
        or [B] per-request positions (continuous batching).
        lens: [B] positions each row attends, ``pos + 1`` by default; 0
        marks a row whose logits are ignored, whose KV the stacked-pool
        attention kernel then does not read.
        """
        cfg = self.cfg
        window = (cfg.sliding_window if windowed else 0) if window is None \
            else window
        x = L.embed_tokens(params["embed"], tokens)
        b = x.shape[0]
        pos = jnp.broadcast_to(
            jnp.atleast_1d(jnp.asarray(pos, jnp.int32)), (b,))
        positions = pos[:, None]
        cos, sin = self._cos_sin(b, positions)

        if cfg.family in ("dense", "vlm", "moe"):
            # the stacked cache rides in the carry, not in xs/ys: each
            # layer writes its B new rows into it in place, so a donated
            # pool is never copied (as ys it is written out whole)
            def scan_layers(lcfg, lparams, stack, x, **kw):
                def body(carry, xs):
                    x, stack = carry
                    lp, i = xs
                    return _dense_layer_decode(
                        lcfg, lp, x, cos, sin, stack, pos, window=window,
                        layer=i, lens=lens, **kw), None
                n = jax.tree.leaves(stack)[0].shape[0]
                (x, stack), _ = jax.lax.scan(
                    body, (x, stack),
                    (lparams, jnp.arange(n, dtype=jnp.int32)))
                return x, stack
            if cfg.family == "moe" and cfg.first_k_dense_layers:
                x, dcache = scan_layers(cfg.with_(num_experts=0),
                                        params["dense_layers"],
                                        cache["dense_layers"], x)
                cache = dict(cache, dense_layers=dcache)
            x, lcache = scan_layers(cfg, params["layers"], cache["layers"], x,
                                    moe_cap=moe_cap,
                                    weight_stationary=weight_stationary)
            cache = dict(cache, layers=lcache)
        elif cfg.family == "ssm":
            def body(carry, xs):
                lp, st = xs
                x = carry
                h = L.apply_norm(lp["ln1"], x, cfg.norm)
                tm, tm_state = R.time_mix_forward(lp["time_mix"], h, cfg,
                                                  state=st)
                x = x + tm
                h = L.apply_norm(lp["ln2"], x, cfg.norm)
                cm, cm_state = R.channel_mix_forward(lp["channel_mix"], h,
                                                     cfg, state=st)
                new_state = {"tm_shift": tm_state["tm_shift"],
                             "wkv": tm_state["wkv"],
                             "cm_shift": cm_state["cm_shift"]}
                return x + cm, new_state
            x, states = jax.lax.scan(body, x,
                                     (params["layers"], cache["layers"]))
            cache = dict(cache, layers=states)
        elif cfg.family == "hybrid":
            x, cache = self._hybrid_decode(params, x, cos, sin, cache, pos)
        elif cfg.family == "audio":
            x, cache = self._decode_audio(params, x, cos, sin, cache, pos)
        else:
            raise ValueError(cfg.family)

        x = L.apply_norm(params["final_norm"], x, cfg.norm)
        logits = L.unembed(params["embed"], x, cfg.logits_softcap)
        return logits[:, 0], cache

    def _hybrid_decode(self, params, x, cos, sin, cache, pos):
        cfg = self.cfg
        period = cfg.attn_layer_period
        g, rem = self.n_hybrid_groups()
        sp = params["shared_attn"]

        def mamba_body(carry, xs):
            lp, st = xs
            h = L.apply_norm(lp["ln"], carry, cfg.norm)
            y, st = M.mamba_decode_step(lp["mamba"], h, cfg, st)
            return carry + y, st

        stacked, mstate = params["layers"], cache["layers"]
        main_p = jax.tree.map(lambda a: a[:g * period].reshape(
            (g, period) + a.shape[1:]), stacked)
        main_s = jax.tree.map(lambda a: a[:g * period].reshape(
            (g, period) + a.shape[1:]), mstate)
        tail_p = jax.tree.map(lambda a: a[g * period:], stacked)
        tail_s = jax.tree.map(lambda a: a[g * period:], mstate)

        def group_body(carry, xs):
            gp, gs, acache = xs
            x, gs = jax.lax.scan(mamba_body, carry, (gp, gs))
            h = L.apply_norm(sp["ln"], x, cfg.norm)
            a, acache = attn.decode_attention(sp["attn"], h, cos, sin, cfg,
                                              acache, pos,
                                              window=cfg.sliding_window)
            x = x + a
            h = L.apply_norm(sp["ln2"], x, cfg.norm)
            x = x + L.apply_mlp(sp["mlp"], h, cfg.activation)
            return x, (gs, acache)

        x, (main_s_new, acaches) = jax.lax.scan(
            group_body, x, (main_p, main_s, cache["shared_attn"]))
        if rem:
            x, tail_s_new = jax.lax.scan(mamba_body, x, (tail_p, tail_s))
        else:
            tail_s_new = tail_s
        new_mstate = jax.tree.map(
            lambda a, b: jnp.concatenate(
                [a.reshape((g * period,) + a.shape[2:]), b], axis=0),
            main_s_new, tail_s_new)
        return x, dict(cache, layers=new_mstate, shared_attn=acaches)

    def _decode_audio(self, params, x, cos, sin, cache, pos):
        cfg = self.cfg
        b = x.shape[0]
        enc_pos = jnp.arange(cfg.encoder_seq, dtype=jnp.int32)
        q_pos = pos[:, None]

        def body(carry, xs):
            lp, lcache, xkv = xs
            x = carry
            h = L.apply_norm(lp["ln1"], x, cfg.norm)
            a, lcache = attn.decode_attention(lp["attn"], h, cos, sin, cfg,
                                              lcache, pos)
            x = x + a
            h = L.apply_norm(lp["ln_x"], x, cfg.norm)
            q = jnp.einsum("bsd,dhe->bshe", h, lp["xattn"]["wq"],
                           preferred_element_type=jnp.float32).astype(x.dtype)
            qg = q.reshape(b, 1, cfg.num_kv_heads,
                           cfg.num_heads // cfg.num_kv_heads, cfg.head_dim)
            o = attn.simple_sdpa(qg, xkv["k"], xkv["v"], q_pos=q_pos,
                                 k_pos=enc_pos, causal=False)
            x = x + attn.out_proj(lp["xattn"], o)
            h = L.apply_norm(lp["ln2"], x, cfg.norm)
            return x + L.apply_mlp(lp["mlp"], h, cfg.activation), lcache

        x, lcaches = jax.lax.scan(
            body, x, (params["layers"], cache["layers"], cache["cross"]))
        return x, dict(cache, layers=lcaches)
