"""Unified language model covering every assigned architecture family.

One ``LM`` class; the config decides the layer stack:
  dense            — [attn, mlp] × L, optionally with a 5:1 local:global
                     grouped pattern (gemma3)
  moe              — [attn|mla, moe_ffn] × L with first_k_dense dense layers
  ssm              — [mamba1] × L
  hybrid           — groups of (attn_every-1) mamba2 layers + one SHARED
                     attention block (zamba2)
  audio (enc-dec)  — whisper: encoder over stub frame embeddings + decoder
                     with self+cross attention
  vlm              — phi3: stub patch embeddings prepended to the token
                     sequence

All stacks are ``lax.scan`` over stacked parameters (compact HLO at 126
layers); mixed/hybrid archs use a grouped scan (outer scan over groups,
inner scan over the homogeneous sub-stack) so no per-layer ``lax.cond``
is ever traced.

Floe integration: every projection accepts per-layer, per-expert LoRA
tensors (core/lora.py) merged with router gate weights ω (Eq. 8) — the
paper's technique is a first-class argument of every entry point.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import attention as ATT
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.sharding_hooks import constrain


def sinusoidal_positions(s: int, d: int, dtype) -> jax.Array:
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, dim / d)
    out = jnp.zeros((s, d), jnp.float32)
    out = out.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
    return out.astype(dtype)


def sinusoidal_at(pos, d: int, dtype) -> jax.Array:
    """Sinusoidal embedding at a single (traced) position -> (d,)."""
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)
    ang = pos.astype(jnp.float32) / jnp.power(10_000.0, dim / d)
    out = jnp.zeros((d,), jnp.float32)
    out = out.at[0::2].set(jnp.sin(ang)).at[1::2].set(jnp.cos(ang))
    return out.astype(dtype)


def _tree_index(tree, idx):
    return jax.tree.map(lambda t: t[idx] if t is not None else None, tree)


def _to_pages(leaf, a, ps: int):
    """Split axis ``a`` (length S) into (ceil(S/ps), ps), zero-padding
    the remainder — the reshape between dense sequence layout and
    per-row page rows."""
    s = leaf.shape[a]
    n = -(-s // ps)
    pad = n * ps - s
    if pad:
        spec = [(0, 0)] * leaf.ndim
        spec[a] = (0, pad)
        leaf = jnp.pad(leaf, spec)
    return leaf.reshape(leaf.shape[:a] + (n, ps) + leaf.shape[a + 1:])


# ===========================================================================
# Layer bodies
# ===========================================================================


def dense_layer_spec(cfg, use_moe: bool = False, d_ff: Optional[int] = None):
    s = {
        "ln1": L.norm_spec(cfg),
        "attn": MLA.mla_spec(cfg) if cfg.use_mla else ATT.attn_spec(cfg),
        "ln2": L.norm_spec(cfg),
    }
    if use_moe:
        s["moe"] = MOE.moe_spec(cfg)
    else:
        s["mlp"] = L.mlp_spec(cfg, d_ff)
    return s


def dense_layer(cfg, p, x, *, positions, mode, cache, lora, gates,
                is_global=True, absorb=False, pages=None):
    """Pre-norm [attn|mla] + [mlp|moe].  Returns (x, new_cache, aux)."""
    h = L.norm(cfg, p["ln1"], x)
    if cfg.use_mla:
        if pages is not None:
            raise NotImplementedError("paged decode: GQA layers only")
        a, new_cache = MLA.mla_block(cfg, p["attn"], h, positions=positions,
                                     lora=lora, gates=gates, cache=cache,
                                     mode=mode, absorb=absorb)
    else:
        a, new_cache = ATT.attention_block(cfg, p["attn"], h,
                                           positions=positions, lora=lora,
                                           gates=gates, is_global=is_global,
                                           cache=cache, mode=mode,
                                           pages=pages)
    x = x + a
    h = L.norm(cfg, p["ln2"], x)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        m, aux = MOE.moe_ffn(cfg, p["moe"], h, lora, gates)
    else:
        m = L.mlp(cfg, p["mlp"], h, (lora or {}).get("mlp_in"),
                  (lora or {}).get("mlp_out"), gates)
    return constrain(x + m, "resid"), new_cache, aux


def ssm_layer_spec(cfg):
    if cfg.ssm_version == 1:
        return {"ln": L.norm_spec(cfg), "ssm": SSM.mamba1_spec(cfg)}
    s = {"ln": L.norm_spec(cfg), "ssm": SSM.mamba2_spec(cfg)}
    if cfg.d_ff:                                   # zamba2 mamba layers: +MLP
        s["ln2"] = L.norm_spec(cfg)
        s["mlp"] = L.mlp_spec(cfg)
    return s


def ssm_layer(cfg, p, x, *, mode, cache, lora, gates, unroll: int = 1):
    h = L.norm(cfg, p["ln"], x)
    block = SSM.mamba1_block if cfg.ssm_version == 1 else SSM.mamba2_block
    y, new_cache = block(cfg, p["ssm"], h, lora=lora, gates=gates,
                         cache=cache, mode=mode, unroll=unroll)
    x = x + y
    if "mlp" in p:
        h = L.norm(cfg, p["ln2"], x)
        x = x + L.mlp(cfg, p["mlp"], h, (lora or {}).get("mlp_in"),
                      (lora or {}).get("mlp_out"), gates)
    return constrain(x, "resid"), new_cache, jnp.zeros((), jnp.float32)


def encoder_layer_spec(cfg):
    return {
        "ln1": L.norm_spec(cfg),
        "attn": ATT.attn_spec(cfg),
        "ln2": L.norm_spec(cfg),
        "mlp": L.mlp_spec(cfg),
    }


def encoder_layer(cfg, p, x, lora, gates):
    h = L.norm(cfg, p["ln1"], x)
    b, s, d = h.shape
    hh, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    get = (lora or {}).get
    q = L.linear(p["attn"]["q"], h, get("q"), gates).reshape(b, s, hh, hd)
    k = L.linear(p["attn"]["k"], h, get("k"), gates).reshape(b, s, kvh, hd)
    v = L.linear(p["attn"]["v"], h, get("v"), gates).reshape(b, s, kvh, hd)
    o = ATT.bidirectional_attention(q, k, v).reshape(b, s, hh * hd)
    x = x + L.linear(p["attn"]["o"], o, get("o"), gates)
    h = L.norm(cfg, p["ln2"], x)
    return x + L.mlp(cfg, p["mlp"], h, get("mlp_in"), get("mlp_out"), gates)


def decoder_layer_spec(cfg):
    return {
        "ln1": L.norm_spec(cfg),
        "self_attn": ATT.attn_spec(cfg),
        "ln_x": L.norm_spec(cfg),
        "cross_attn": ATT.attn_spec(cfg),
        "ln2": L.norm_spec(cfg),
        "mlp": L.mlp_spec(cfg),
    }


def decoder_layer(cfg, p, x, *, positions, enc, mode, cache, lora, gates):
    """Whisper decoder layer.  cache = {"k","v","xk","xv"}; enc: encoder out
    (needed when cross K/V are not yet cached, i.e. train)."""
    b, s, d = x.shape
    hh, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    get = (lora or {}).get
    # self attention (causal, cached)
    h = L.norm(cfg, p["ln1"], x)
    self_cache = None if mode == "train" else \
        ({"k": cache["k"], "v": cache["v"]} if mode == "decode" else None)
    a, new_self = ATT.attention_block(cfg, p["self_attn"], h,
                                      positions=positions, lora=lora,
                                      gates=gates, cache=self_cache,
                                      mode=mode, rope_enabled=False)
    x = x + a
    # cross attention
    h = L.norm(cfg, p["ln_x"], x)
    q = L.linear(p["cross_attn"]["q"], h, get("q"), gates).reshape(b, s, hh, hd)
    if mode == "decode":
        xk, xv = cache["xk"], cache["xv"]
    else:
        xk = L.linear(p["cross_attn"]["k"], enc).reshape(
            b, enc.shape[1], kvh, hd)
        xv = L.linear(p["cross_attn"]["v"], enc).reshape(
            b, enc.shape[1], kvh, hd)
    o = ATT.bidirectional_attention(q, xk, xv).reshape(b, s, hh * hd)
    x = x + L.linear(p["cross_attn"]["o"], o, get("o"), gates)
    h = L.norm(cfg, p["ln2"], x)
    x = x + L.mlp(cfg, p["mlp"], h, get("mlp_in"), get("mlp_out"), gates)
    new_cache = None
    if mode == "prefill":
        new_cache = {"k": new_self["k"], "v": new_self["v"], "xk": xk, "xv": xv}
    elif mode == "decode":
        new_cache = {"k": new_self["k"], "v": new_self["v"],
                     "xk": cache["xk"], "xv": cache["xv"]}
    return x, new_cache, jnp.zeros((), jnp.float32)


# ===========================================================================
# LM
# ===========================================================================


def _stack_specs(spec: Dict, n: Tuple[int, ...]) -> Dict:
    """Prepend stacking dims to every P in a spec tree."""
    def f(p: L.P) -> L.P:
        return L.P(tuple(n) + p.shape, (None,) * len(n) + p.axes,
                   p.init, p.scale)
    return jax.tree.map(f, spec, is_leaf=lambda x: isinstance(x, L.P))


class LM:
    """Functional model bundle for one ModelConfig."""

    def __init__(self, cfg, remat: bool = True, unroll_layers: bool = False,
                 ssm_unroll: int = 1, ring_cache: bool = False):
        self.cfg = cfg
        self.remat = remat
        # ring_cache (§Perf): sliding-window layers keep a window-sized
        # ring buffer instead of a full-sequence cache
        self.ring_cache = ring_cache
        # unroll_layers: unroll the layer scans (dry-run accuracy: XLA
        # cost_analysis counts while-loop bodies ONCE; unrolling restores
        # exact FLOP/collective accounting — see launch/analysis.py)
        self.unroll_layers = unroll_layers
        # ssm_unroll: unroll factor of the mamba chunk scan (2-point
        # FLOP-correction probe in launch/dryrun.py)
        self.ssm_unroll = ssm_unroll
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------- layout
    def _layout(self):
        """Stack layout: (kind, n_groups, group_size, tail)."""
        cfg = self.cfg
        if cfg.family == "hybrid" and cfg.attn_every:
            g = cfg.attn_every
            n_groups = cfg.num_layers // g
            tail = cfg.num_layers - n_groups * g
            return ("grouped", n_groups, g, tail)
        if cfg.attn_type == "mixed" and cfg.global_every:
            g = cfg.global_every
            n_groups = cfg.num_layers // g
            tail = cfg.num_layers - n_groups * g
            return ("grouped", n_groups, g, tail)
        return ("plain", cfg.num_layers, 1, 0)

    # -------------------------------------------------------------- specs
    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        s: Dict[str, Any] = {"embed": L.embed_spec(cfg),
                             "ln_f": L.norm_spec(cfg)}
        kind, n_groups, g, tail = self._layout()

        if cfg.family == "audio":
            s["enc"] = _stack_specs(encoder_layer_spec(cfg),
                                    (cfg.encoder_layers,))
            s["enc_ln"] = L.norm_spec(cfg)
            s["dec"] = _stack_specs(decoder_layer_spec(cfg),
                                    (cfg.num_layers,))
            return s
        if cfg.family == "vlm":
            s["proj"] = L.linear_spec(cfg.d_model, cfg.d_model,
                                      "d_model", "d_model")
        if cfg.family == "ssm":
            s["layers"] = _stack_specs(ssm_layer_spec(cfg), (cfg.num_layers,))
            return s
        if cfg.family == "hybrid":
            # inner mamba2 layers grouped; one SHARED attention block
            s["inner"] = _stack_specs(ssm_layer_spec(cfg), (n_groups, g - 1))
            s["tail"] = _stack_specs(ssm_layer_spec(cfg), (tail,))
            s["shared_attn"] = dense_layer_spec(cfg)   # weight-tied block
            return s
        if cfg.family == "moe":
            kd = cfg.first_k_dense
            if kd:
                s["dense_layers"] = _stack_specs(
                    dense_layer_spec(cfg, use_moe=False), (kd,))
            s["layers"] = _stack_specs(
                dense_layer_spec(cfg, use_moe=True), (cfg.num_layers - kd,))
            return s
        # dense (incl. gemma3 mixed + vlm backbone)
        if kind == "grouped":
            s["inner"] = _stack_specs(dense_layer_spec(cfg), (n_groups, g - 1))
            s["tail"] = _stack_specs(dense_layer_spec(cfg), (tail,))
            s["global_layers"] = _stack_specs(dense_layer_spec(cfg),
                                              (n_groups,))
        else:
            s["layers"] = _stack_specs(dense_layer_spec(cfg),
                                       (cfg.num_layers,))
        return s

    def lora_layout(self) -> Dict[str, Tuple[Tuple[int, ...], Dict[str, Tuple[int, int]]]]:
        """{stack_key: (stack_dims, {target: (d_in, d_out)})} — the contract
        between core/lora.py adapter trees and ``_run_stack`` lora slicing."""
        cfg = self.cfg
        kind, n_groups, g, tail = self._layout()
        d, f = cfg.d_model, cfg.d_ff
        h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        gate_mult = 2 if cfg.mlp_type in ("swiglu", "geglu") else 1

        def attn_targets():
            if cfg.use_mla:
                return {"q": (d, cfg.q_lora_rank),
                        "kv": (d, cfg.kv_lora_rank + cfg.qk_rope_dim),
                        "o": (h * cfg.v_head_dim, d)}
            return {"q": (d, h * hd), "k": (d, kv * hd), "v": (d, kv * hd),
                    "o": (h * hd, d)}

        def mlp_targets(ff=None):
            ff = ff or f
            return {"mlp_in": (d, gate_mult * ff), "mlp_out": (ff, d)}

        def ssm_targets():
            di, n = cfg.d_inner, cfg.ssm_state
            if cfg.ssm_version == 1:
                return {"ssm_in": (d, 2 * di),
                        "ssm_x": (di, cfg.dt_rank + 2 * n),
                        "ssm_dt": (cfg.dt_rank, di),
                        "ssm_out": (di, d)}
            proj = 2 * di + 2 * cfg.ssm_ngroups * n + cfg.ssm_nheads
            t = {"ssm_in": (d, proj), "ssm_out": (di, d)}
            if cfg.d_ff:
                t.update(mlp_targets())
            return t

        if cfg.family == "audio":
            t = {**attn_targets(), **mlp_targets()}
            return {"enc": ((cfg.encoder_layers,), t),
                    "dec": ((cfg.num_layers,), t)}
        if cfg.family == "ssm":
            return {"layers": ((cfg.num_layers,), ssm_targets())}
        if cfg.family == "hybrid":
            at = {**attn_targets(), **mlp_targets()}
            return {"inner": ((n_groups, g - 1), ssm_targets()),
                    "tail": ((tail,), ssm_targets()),
                    "special": ((n_groups,), at)}
        if cfg.family == "moe":
            kd = cfg.first_k_dense
            # MoE layers: adapters on attention (+ shared expert if present)
            mt = dict(attn_targets())
            if cfg.num_shared_experts:
                mt.update(mlp_targets(cfg.moe_d_ff * cfg.num_shared_experts))
            out = {"layers": ((cfg.num_layers - kd,), mt)}
            if kd:
                out["dense_layers"] = ((kd,),
                                       {**attn_targets(), **mlp_targets()})
            return out
        t = {**attn_targets(), **mlp_targets()}
        if kind == "grouped":
            return {"inner": ((n_groups, g - 1), t), "tail": ((tail,), t),
                    "special": ((n_groups,), t)}
        return {"layers": ((cfg.num_layers,), t)}

    def init(self, key, shardings=None) -> Dict[str, Any]:
        """Random params; ``shardings`` (e.g. a serving deployment's
        ``model_param_shardings``) draws each leaf in place."""
        return L.materialize(self.param_specs(), key, self.dtype, shardings)

    def abstract_params(self):
        return L.abstract_params(self.param_specs(), self.dtype)

    def param_axes(self):
        return L.axes_tree(self.param_specs())

    # -------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_seq: int) -> Dict[str, Any]:
        cfg = self.cfg
        dt = self.dtype
        kind, n_groups, g, tail = self._layout()

        def attn_kv(n_layers):
            kv, hd = cfg.num_kv_heads, cfg.head_dim
            return {"k": jnp.zeros((n_layers, batch, max_seq, kv, hd), dt),
                    "v": jnp.zeros((n_layers, batch, max_seq, kv, hd), dt)}

        def ssm_state(n: Tuple[int, ...]):
            if cfg.ssm_version == 1:
                h = jnp.zeros(n + (batch, cfg.d_inner, cfg.ssm_state),
                              jnp.float32)
                cw = cfg.d_inner
            else:
                h = jnp.zeros(n + (batch, cfg.ssm_nheads, cfg.ssm_head_dim,
                                   cfg.ssm_state), jnp.float32)
                cw = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
            conv = jnp.zeros(n + (batch, cfg.ssm_conv - 1, cw), dt)
            return {"conv": conv, "h": h}

        if cfg.family == "audio":
            kv, hd = cfg.num_kv_heads, cfg.head_dim
            nl, fs = cfg.num_layers, cfg.encoder_seq
            c = attn_kv(nl)
            c["xk"] = jnp.zeros((nl, batch, fs, kv, hd), dt)
            c["xv"] = jnp.zeros((nl, batch, fs, kv, hd), dt)
            c["pos"] = jnp.zeros((), jnp.int32)
            return c
        if cfg.family == "ssm":
            c = ssm_state((cfg.num_layers,))
            c["pos"] = jnp.zeros((), jnp.int32)
            return c
        if cfg.family == "hybrid":
            kv, hd = cfg.num_kv_heads, cfg.head_dim
            attn_seq = min(max_seq, cfg.sliding_window) \
                if (self.ring_cache and cfg.attn_type == "sliding") \
                else max_seq
            attn_c = {"k": jnp.zeros((n_groups, batch, attn_seq, kv, hd),
                                     dt),
                      "v": jnp.zeros((n_groups, batch, attn_seq, kv, hd),
                                     dt)}
            return {"inner": ssm_state((n_groups, g - 1)),
                    "tail": ssm_state((tail,)),
                    "attn": attn_c,
                    "pos": jnp.zeros((), jnp.int32)}
        if cfg.family == "moe":
            kd = cfg.first_k_dense
            def mla_c(n):
                return {"c": jnp.zeros((n, batch, max_seq, cfg.kv_lora_rank),
                                       dt),
                        "kr": jnp.zeros((n, batch, max_seq, cfg.qk_rope_dim),
                                        dt)}
            sub = mla_c if cfg.use_mla else attn_kv
            return {"dense": sub(kd), "moe": sub(cfg.num_layers - kd),
                    "pos": jnp.zeros((), jnp.int32)}
        if kind == "grouped":                       # gemma3
            kv, hd = cfg.num_kv_heads, cfg.head_dim
            local_seq = min(max_seq, cfg.sliding_window) \
                if self.ring_cache else max_seq
            def kv_c(n, seq=max_seq):
                return {"k": jnp.zeros(n + (batch, seq, kv, hd), dt),
                        "v": jnp.zeros(n + (batch, seq, kv, hd), dt)}
            return {"inner": kv_c((n_groups, g - 1), local_seq),
                    "tail": kv_c((tail,), local_seq),
                    "global": kv_c((n_groups,)),
                    "pos": jnp.zeros((), jnp.int32)}
        c = attn_kv(cfg.num_layers)
        if cfg.family == "moe":
            pass
        c["pos"] = jnp.zeros((), jnp.int32)
        return c

    def abstract_cache(self, batch: int, max_seq: int):
        return jax.eval_shape(lambda: self.init_cache(batch, max_seq))

    # ------------------------------------------------------------- embed
    def _embed_inputs(self, params, batch_d, mode):
        cfg = self.cfg
        tokens = batch_d["tokens"]
        x = L.embed(cfg, params["embed"], tokens)
        if cfg.family == "vlm" and mode != "decode":
            patches = batch_d["patches"].astype(x.dtype)
            patches = L.linear(params["proj"], patches)
            x = jnp.concatenate([patches, x], axis=1)
        return x

    # --------------------------------------------------------- stack run
    def _run_stack(self, params, x, *, positions, mode, cache, lora, gates,
                   enc=None, absorb=False, pages=None):
        """Dispatch to the family stack.  Returns (x, new_cache, aux).

        ``pages``: block tables for paged decode (cache leaves are page
        pools).  In prefill mode a non-None ``cache`` is a shared-prefix
        attention HISTORY ({"k","v","hpos"} per stack kind) and the
        returned cache covers only the fresh suffix positions."""
        cfg = self.cfg
        kind, n_groups, g, tail = self._layout()
        remat = self.remat and mode == "train"

        def wrap(fn):
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.nothing_saveable
            ) if remat else fn

        def scan_layers(body, x, stack_p, stack_c, stack_l, length):
            """Scan `body` over stacked params (+cache xs, +lora xs)."""
            def f(carry, xs):
                xx, aux = carry
                p_i, c_i, l_i = xs
                xx, nc, a = body(xx, p_i, c_i, l_i)
                return (xx, aux + a), nc
            xs = (stack_p, stack_c, stack_l)
            (x, aux), new_c = jax.lax.scan(f, (x, jnp.zeros((), jnp.float32)),
                                           xs, length=length,
                                           unroll=length if
                                           (self.unroll_layers and length)
                                           else 1)
            return x, new_c, aux

        def no_cache(n):
            return None

        # ------- bodies ---------------------------------------------------
        def dense_body(is_global=True):
            def body(xx, p_i, c_i, l_i):
                return wrap(lambda a, b, c, d: dense_layer(
                    cfg, b, a, positions=positions, mode=mode, cache=c,
                    lora=d, gates=gates, is_global=is_global, absorb=absorb,
                    pages=pages)
                )(xx, p_i, c_i, l_i)
            return body

        def ssm_body(xx, p_i, c_i, l_i):
            return wrap(lambda a, b, c, d: ssm_layer(
                cfg, b, a, mode=mode, cache=c, lora=d, gates=gates,
                unroll=self.ssm_unroll)
            )(xx, p_i, c_i, l_i)

        lget = lora or {}

        if cfg.family == "audio":
            # encoder (train/prefill only)
            if mode != "decode":
                e = enc
                def ebody(carry, xs):
                    p_i, l_i = xs
                    return encoder_layer(cfg, p_i, carry, l_i, gates), None
                e, _ = jax.lax.scan(ebody, e,
                                    (params["enc"], lget.get("enc")),
                                    unroll=cfg.encoder_layers
                                    if self.unroll_layers else 1)
                e = L.norm(cfg, params["enc_ln"], e)
            else:
                e = None
            def dbody(carry, xs):
                xx, aux = carry
                p_i, c_i, l_i = xs
                xx, nc, a = decoder_layer(cfg, p_i, xx, positions=positions,
                                          enc=e, mode=mode, cache=c_i,
                                          lora=l_i, gates=gates)
                return (xx, aux + a), nc
            c_xs = None if mode == "train" else \
                {k: cache[k] for k in ("k", "v", "xk", "xv")} if mode == "decode" \
                else None
            (x, aux), new_c = jax.lax.scan(
                f=dbody, init=(x, jnp.zeros((), jnp.float32)),
                xs=(params["dec"], c_xs, lget.get("dec")),
                length=cfg.num_layers,
                unroll=cfg.num_layers if self.unroll_layers else 1)
            new_cache = None
            if mode != "train" and new_c is not None:
                new_cache = dict(new_c)
            return x, new_cache, aux

        if cfg.family == "ssm":
            c_xs = {k: cache[k] for k in ("conv", "h")} if mode == "decode" \
                else None
            x, new_c, aux = scan_layers(ssm_body, x, params["layers"], c_xs,
                                        lget.get("layers"), cfg.num_layers)
            new_cache = None
            if mode in ("prefill", "decode") and new_c is not None:
                new_cache = dict(new_c)
            return x, new_cache, aux

        if cfg.family == "hybrid" or kind == "grouped":
            is_hybrid = cfg.family == "hybrid"
            inner_body = ssm_body if is_hybrid else dense_body(is_global=False)
            special_body = dense_body(is_global=True)
            special_params = params["shared_attn"] if is_hybrid \
                else None  # per-group global layers for gemma3

            inner_c = special_c = tail_c = None
            if mode == "decode" or (mode == "prefill" and cache is not None):
                inner_c = cache["inner"]
                tail_c = cache["tail"]
                special_c = cache["attn"] if is_hybrid else cache["global"]

            aux_total = jnp.zeros((), jnp.float32)

            def group_step(carry, xs):
                xx, aux = carry
                in_p, sp_p, in_c, sp_c, in_l, sp_l = xs
                xx, nic, a1 = scan_layers(inner_body, xx, in_p, in_c, in_l,
                                          g - 1)
                sp = special_params if is_hybrid else sp_p
                xx, nsc, a2 = special_body(xx, sp, sp_c, sp_l)
                return (xx, aux + a1 + a2), (nic, nsc)

            sp_p_stack = None if is_hybrid else params["global_layers"]
            in_l = (lget.get("inner"))
            sp_l = (lget.get("special"))
            (x, aux_total), (new_in_c, new_sp_c) = jax.lax.scan(
                group_step, (x, aux_total),
                (params["inner"], sp_p_stack, inner_c, special_c, in_l, sp_l),
                length=n_groups,
                unroll=n_groups if self.unroll_layers else 1)
            # tail (length may be 0 — lax.scan handles the empty stack)
            tl = lget.get("tail")
            x, new_tail_c, a3 = scan_layers(inner_body, x, params["tail"],
                                            tail_c, tl, tail)
            aux_total = aux_total + a3

            new_cache = None
            if mode in ("prefill", "decode"):
                key_sp = "attn" if is_hybrid else "global"
                new_cache = {"inner": new_in_c, key_sp: new_sp_c,
                             "tail": new_tail_c}
            return x, new_cache, aux_total

        if cfg.family == "moe":
            kd = cfg.first_k_dense
            aux_total = jnp.zeros((), jnp.float32)
            dense_c = moe_c = None
            if mode == "decode":
                dense_c = {k: cache["dense"][k] for k in cache["dense"]}
                moe_c = {k: cache["moe"][k] for k in cache["moe"]}
            new_dense_c = None
            if kd:
                x, new_dense_c, a = scan_layers(dense_body(), x,
                                                params["dense_layers"],
                                                dense_c,
                                                lget.get("dense_layers"), kd)
                aux_total = aux_total + a
            x, new_moe_c, a = scan_layers(dense_body(), x, params["layers"],
                                          moe_c, lget.get("layers"),
                                          cfg.num_layers - kd)
            aux_total = aux_total + a
            new_cache = None
            if mode in ("prefill", "decode"):
                new_cache = {"dense": new_dense_c, "moe": new_moe_c}
            return x, new_cache, aux_total

        # plain dense
        c_xs = None
        if mode == "decode":
            c_xs = {"k": cache["k"], "v": cache["v"]}
        elif mode == "prefill" and cache is not None:
            # shared-prefix history threaded per layer as scan xs
            c_xs = {"k": cache["k"], "v": cache["v"], "hpos": cache["hpos"]}
        x, new_c, aux = scan_layers(dense_body(), x, params["layers"], c_xs,
                                    lget.get("layers"), cfg.num_layers)
        new_cache = dict(new_c) if (mode in ("prefill", "decode")
                                    and new_c is not None) else None
        return x, new_cache, aux

    # ------------------------------------------------------- entry points
    def train_logits(self, params, batch_d, lora=None, gates=None):
        """Full-sequence causal logits.  Returns (logits, aux_loss)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch_d, "train")
        s = x.shape[1]
        positions = jnp.arange(s)
        enc = None
        if cfg.family == "audio":
            f = batch_d["frames"].astype(x.dtype)
            enc = f + sinusoidal_positions(f.shape[1], cfg.d_model, x.dtype)
            x = x + sinusoidal_positions(s, cfg.d_model, x.dtype)
        x, _, aux = self._run_stack(params, x, positions=positions,
                                    mode="train", cache=None, lora=lora,
                                    gates=gates, enc=enc)
        x = L.norm(cfg, params["ln_f"], x)
        return L.unembed(cfg, params["embed"], x), aux

    def prefill(self, params, batch_d, max_seq: int, lora=None, gates=None):
        """Process the prompt, build the cache.  Returns (last_logits, cache)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch_d, "prefill")
        b, s = x.shape[0], x.shape[1]
        positions = jnp.arange(s)
        enc = None
        if cfg.family == "audio":
            f = batch_d["frames"].astype(x.dtype)
            enc = f + sinusoidal_positions(f.shape[1], cfg.d_model, x.dtype)
            x = x + sinusoidal_positions(s, cfg.d_model, x.dtype)
        x, pc, _ = self._run_stack(params, x, positions=positions,
                                   mode="prefill", cache=None, lora=lora,
                                   gates=gates, enc=enc)
        x = L.norm(cfg, params["ln_f"], x[:, -1:])
        logits = L.unembed(cfg, params["embed"], x)
        cache = self._pad_cache(pc, b, s, max_seq)
        return logits, cache

    def prefill_packed(self, params, batch_d, lengths, max_seq: int,
                       lora=None, gates=None):
        """Packed ragged-batch prefill: B>1 prompts right-padded to one
        shared length, processed in a single call.

        batch_d["tokens"]: (B, Lpad); lengths: (B,) valid token counts.
        Causal masking keeps every valid position independent of the
        rows' padding, so row b's cache[0:lengths[b]] and its last-token
        logits match a B=1 prefill of the unpadded prompt; pad positions
        hold garbage that decode never attends (its mask is
        kv_pos <= pos_b, and pos_b starts at lengths[b]).

        Returns (last_logits (B,1,V), cache) with PER-ROW cache["pos"]
        = lengths, ready for continuous-batching decode."""
        cfg = self.cfg
        if cfg.family in ("audio", "vlm"):
            raise NotImplementedError(
                "packed prefill: token-only families (got "
                f"{cfg.family})")
        x = self._embed_inputs(params, batch_d, "prefill")
        b, s = x.shape[0], x.shape[1]
        positions = jnp.arange(s)
        x, pc, _ = self._run_stack(params, x, positions=positions,
                                   mode="prefill", cache=None, lora=lora,
                                   gates=gates)
        lengths = jnp.asarray(lengths, jnp.int32)
        # per-row last VALID position (shared x[:, -1:] would read padding)
        idx = jnp.clip(lengths - 1, 0)[:, None, None]
        last = jnp.take_along_axis(x, idx, axis=1)           # (B, 1, d)
        last = L.norm(cfg, params["ln_f"], last)
        logits = L.unembed(cfg, params["embed"], last)
        cache = self._pad_cache(pc, b, s, max_seq, lengths=lengths)
        return logits, cache

    def _pad_cache(self, pc, b, s, max_seq, lengths=None):
        """Embed prefill cache (len s) into a max_seq cache.

        ``lengths`` (B,) switches to packed ragged-batch semantics: "pos"
        becomes per-row, and ring (window < s) placement gathers each
        row's own last-`w` positions into slot p % w instead of the
        shared roll (rows at different depths wrap differently)."""
        cfg = self.cfg
        full = self.init_cache(b, max_seq)

        def ring_rowwise(dst, src, a):
            # slot j of row b holds the ring_kv_positions invariant at
            # depth len_b-1; every KV cache layout stacks the batch axis
            # immediately before the sequence axis, so a-1 is the row axis
            w, s_len = dst.shape[a], src.shape[a]
            p = ATT.ring_kv_positions(
                jnp.asarray(lengths, jnp.int32) - 1, w)        # (B, w)
            idx = jnp.clip(p, 0, s_len - 1)
            shape = [1] * src.ndim
            shape[a - 1] = idx.shape[0]
            shape[a] = w
            return jnp.take_along_axis(src, idx.reshape(shape),
                                       axis=a).astype(dst.dtype)

        def place(dst, src):
            if src is None or not hasattr(dst, "shape"):
                return dst
            if dst.ndim >= 3 and src.ndim == dst.ndim and \
                    dst.shape != src.shape:
                # sequence axis is the one that differs
                ax = [i for i in range(dst.ndim)
                      if dst.shape[i] != src.shape[i]]
                if len(ax) == 1:
                    a = ax[0]
                    if dst.shape[a] >= src.shape[a]:
                        pad = [(0, 0)] * dst.ndim
                        pad[a] = (0, dst.shape[a] - src.shape[a])
                        return jnp.pad(src.astype(dst.dtype), pad)
                    if lengths is not None:
                        return ring_rowwise(dst, src, a)
                    # ring placement: keep the last `w` positions, rolled
                    # so position p lands in slot p % w
                    w, s_len = dst.shape[a], src.shape[a]
                    last = jax.lax.slice_in_dim(src, s_len - w, s_len,
                                                axis=a)
                    return jnp.roll(last.astype(dst.dtype),
                                    (s_len - w) % w, axis=a)
            return src.astype(dst.dtype)

        out = {}
        for k, v in full.items():
            if k == "pos":
                out[k] = jnp.asarray(s, jnp.int32) if lengths is None \
                    else jnp.asarray(lengths, jnp.int32)
            elif isinstance(v, dict) and pc.get(k) is not None:
                out[k] = jax.tree.map(place, v, pc[k])
            elif pc.get(k) is not None:
                out[k] = place(v, pc[k])
            else:
                out[k] = v
        return out

    # ------------------------------------------------- paged KV layout
    # Every GQA cache leaf ends in (..., B, S, KV, hd); the helpers
    # below rely on that trailing layout (seq at -3, batch at -4), so
    # no per-leaf axis metadata is needed on the model side.

    def cache_batch_axes_tree(self, max_seq: int):
        """Per-leaf batch-axis index of the lane cache (-1 batch-free),
        discovered structurally: the axis whose extent follows batch."""
        a = jax.eval_shape(lambda: self.init_cache(2, max_seq))
        b = jax.eval_shape(lambda: self.init_cache(3, max_seq))

        def ax(x, y):
            for i, (m, n) in enumerate(zip(x.shape, y.shape)):
                if m != n:
                    return i
            return -1

        return jax.tree.map(ax, a, b)

    def cache_to_page_rows(self, cache, page_size: int, max_seq: int):
        """Dense lane cache -> per-row page rows: each KV leaf
        (..., B, S, KV, hd) becomes (..., B, ceil(S/ps), ps, KV, hd);
        "pos" and other leaves pass through.  Pure reshape — the dense
        prefill stays the source of truth (bit-identity with the dense
        oracle) and this is the layout step before the pool scatter."""
        axes = self.cache_batch_axes_tree(max_seq)

        def f(leaf, ab):
            if ab < 0 or getattr(leaf, "ndim", 0) < 3:
                return leaf
            return _to_pages(leaf, ab + 1, page_size)

        return jax.tree.map(f, cache, axes)

    def _ring_local_len(self, max_seq: int) -> int:
        """Window extent of ring/local cache leaves (0 when every leaf
        is full-length)."""
        kind, *_ = self._layout()
        if kind == "grouped" and self.ring_cache and \
                self.cfg.attn_type in ("sliding", "mixed"):
            w = min(max_seq, self.cfg.sliding_window)
            if w < max_seq:
                return w
        return 0

    # ------------------------------------------- speculative rollback
    # A speculative draft/verify burst runs up to k masked decode steps
    # whose KV scatters land at positions [pos0, pos0+k) of every
    # attention leaf.  ``spec_snapshot`` captures exactly those write
    # targets beforehand; ``spec_restore`` puts back every slot at or
    # past the per-row accepted count, so a rejected draft suffix
    # leaves the cache bitwise as if it was never decoded.  Both mirror
    # the decode write path's slot arithmetic and ``mode="drop"``
    # discipline (attention.py): full leaves write slot p (dropped at
    # p >= S), ring/local leaves slot p % window, paged leaves go
    # through the row's block/local table — and freed rows
    # (pos >= FREED_POS) never wrote, so they never restore.

    def _spec_kinds(self, cache, max_seq: int):
        """(kind-name, is_local) pairs of the lane cache's KV kinds.
        Name "" addresses the top-level {"k","v"} of the plain layout."""
        if self.cfg.family != "dense":
            raise NotImplementedError(
                "speculative rollback: dense-family caches only "
                f"(got {self.cfg.family})")
        kind, *_ = self._layout()
        if kind == "plain":
            return [("", False)]
        local = self._ring_local_len(max_seq) > 0
        return [("inner", local), ("tail", local), ("global", False)]

    def _spec_slots(self, cache, leaf, pos0, k: int, is_local: bool,
                    max_seq: int):
        """(targets, sentinel) for the k decode writes of one KV leaf:
        dense slot indices or paged flat pool indices, shape (B, k),
        with ``sentinel`` (one past the extent) marking entries the
        decode write path would have dropped."""
        idx = pos0[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
        alive = pos0[:, None] < ATT.FREED_POS
        if "block" not in cache:
            s_len = leaf.shape[-3]
            if is_local:
                return jnp.where(alive, idx % s_len, s_len), s_len
            return jnp.where(alive & (idx < s_len), idx, s_len), s_len
        ps = leaf.shape[-3]
        cap = leaf.shape[-4] * ps
        if is_local:
            s = idx % self._ring_local_len(max_seq)
            tbl = cache["local"]
            ok = alive
        else:
            s = idx
            tbl = cache["block"]
            ok = alive & (s < tbl.shape[1] * ps)
        page = jnp.take_along_axis(
            tbl, jnp.clip(s // ps, 0, tbl.shape[1] - 1), axis=1)
        # NO_PAGE entries put flat past ``cap`` on their own (NO_PAGE*ps
        # >> pool slots), landing in the same drop bucket
        return jnp.where(ok, page * ps + s % ps, cap), cap

    def spec_snapshot(self, cache, pos0, k: int, max_seq: int):
        """Snapshot the k decode-write targets [pos0, pos0+k) of every
        KV leaf before a speculative burst.  pos0: (B,) per-row depth.
        Returns {kind: {"k"/"v": (..., B, k, KV, hd)}} for
        ``spec_restore``; dropped/freed targets snapshot garbage that
        restore skips with the same sentinel arithmetic."""
        pos0 = jnp.asarray(pos0, jnp.int32)
        paged = "block" in cache

        def grab(leaf, is_local):
            slot, cap = self._spec_slots(cache, leaf, pos0, k, is_local,
                                         max_seq)
            if paged:
                fl = leaf.reshape(leaf.shape[:-4] + (cap,)
                                  + leaf.shape[-2:])
                g = jnp.take(fl, jnp.clip(slot, 0, cap - 1).reshape(-1),
                             axis=-3)
                return g.reshape(leaf.shape[:-4] + slot.shape
                                 + leaf.shape[-2:])
            g = jnp.clip(slot, 0, leaf.shape[-3] - 1)
            g = g.reshape((1,) * (leaf.ndim - 4) + g.shape + (1, 1))
            return jnp.take_along_axis(leaf, g, axis=-3)

        out = {}
        for name, is_local in self._spec_kinds(cache, max_seq):
            sub = cache if name == "" else cache[name]
            out[name] = {c: grab(sub[c], is_local) for c in ("k", "v")}
        return out

    def spec_restore(self, cache, snap, pos0, keep, max_seq: int):
        """Roll back a speculative write window: restore slot pos0+j of
        every KV leaf from ``snap`` for every j >= keep[b] (the
        rejected suffix), leaving j < keep[b] (the accepted writes) in
        place.  keep: (B,) int32; keep[b] = k restores nothing for row
        b, keep[b] = 0 rolls the whole window back.  Returns the cache
        with KV leaves rewritten; "pos" is untouched (the caller owns
        the position fixup)."""
        pos0 = jnp.asarray(pos0, jnp.int32)
        keep = jnp.asarray(keep, jnp.int32)
        paged = "block" in cache
        kinds = self._spec_kinds(cache, max_seq)
        first = snap[kinds[0][0]]["k"]
        k = first.shape[-3]
        roll = jnp.arange(k, dtype=jnp.int32)[None, :] >= keep[:, None]

        def put(leaf, sv, is_local):
            slot, cap = self._spec_slots(cache, leaf, pos0, k, is_local,
                                         max_seq)
            slot = jnp.where(roll, slot, cap)
            if paged:
                fl = leaf.reshape(leaf.shape[:-4] + (cap,)
                                  + leaf.shape[-2:])
                fl = fl.at[..., slot, :, :].set(sv.astype(leaf.dtype),
                                                mode="drop")
                return fl.reshape(leaf.shape)
            rows = jnp.arange(leaf.shape[-4])[:, None]
            return leaf.at[..., rows, slot, :, :].set(
                sv.astype(leaf.dtype), mode="drop")

        out = dict(cache)
        for name, is_local in kinds:
            sub = cache if name == "" else cache[name]
            new = {c: put(sub[c], snap[name][c], is_local)
                   for c in ("k", "v")}
            if name == "":
                out.update(new)
            else:
                out[name] = dict(sub, **new)
        return out

    def build_prefix(self, params, tokens, lora=None, gates=None):
        """Prefill a shared preamble ONCE (B=1) -> attention history.

        tokens: (1, pre_len).  Returns a tree shaped like the prefill
        cache whose KV leaves stay LINEAR over all pre_len positions,
        each stack kind annotated with "hpos" (per-layer absolute slot
        positions) — the ``history`` argument of ``prefill_suffix``.
        Causality makes these values bitwise what a full-prompt prefill
        computes at the same positions, independent of any suffix."""
        x = self._embed_inputs(params, {"tokens": tokens}, "prefill")
        pre = x.shape[1]
        x, pc, _ = self._run_stack(params, x, positions=jnp.arange(pre),
                                   mode="prefill", cache=None, lora=lora,
                                   gates=gates)

        def annotate(sub):
            lead = sub["k"].shape[:-4]
            return dict(sub, hpos=jnp.broadcast_to(jnp.arange(pre),
                                                   lead + (pre,)))

        if "k" in pc:
            return annotate(pc)
        return {k: annotate(v) for k, v in pc.items()}

    def extend_history(self, history, suffix_cache):
        """Append a chunk's fresh KV to a ``build_prefix`` history.

        Chunked long-prompt prefill streams a prompt page-chunk by
        page-chunk: each middle chunk runs ``prefill_suffix`` against
        the history so far, then extends it here for the next chunk.
        The suffix must be EXACT-width (B=1, no padding) so absolute
        positions stay contiguous — ``hpos`` gains pre + [0, s)."""

        def ext(hsub, ssub):
            pre = hsub["hpos"].shape[-1]
            s = ssub["k"].shape[-3]
            lead = hsub["k"].shape[:-4]
            out = {k: jnp.concatenate(
                [hsub[k], jnp.broadcast_to(
                    ssub[k], hsub[k].shape[:-3] + ssub[k].shape[-3:])],
                axis=-3) for k in ("k", "v")}
            out["hpos"] = jnp.concatenate(
                [hsub["hpos"],
                 jnp.broadcast_to(pre + jnp.arange(s), lead + (s,))],
                axis=-1)
            return out

        if "k" in history:
            return ext(history, suffix_cache)
        return {kn: ext(history[kn], suffix_cache[kn]) for kn in history}

    def prefill_suffix(self, params, batch_d, lengths, history,
                       pre_len: int, lora=None, gates=None):
        """Packed ragged-batch prefill of prompt SUFFIXES sharing one
        prefix history (``build_prefix`` output).

        batch_d["tokens"]: (B, s_pad) right-padded suffixes; lengths:
        (B,) valid suffix token counts.  Queries run at absolute
        positions pre_len + [0, s_pad) against [history; fresh KV], so
        row b's last-token logits and its suffix KV match a full-prompt
        packed prefill bitwise.  Returns (last_logits (B,1,V),
        suffix_cache) — suffix_cache covers only the fresh positions."""
        cfg = self.cfg
        if cfg.family in ("audio", "vlm", "ssm", "hybrid"):
            raise NotImplementedError(
                f"suffix prefill: attention families only (got {cfg.family})")
        x = self._embed_inputs(params, batch_d, "prefill")
        s = x.shape[1]
        positions = pre_len + jnp.arange(s)
        x, pc, _ = self._run_stack(params, x, positions=positions,
                                   mode="prefill", cache=history, lora=lora,
                                   gates=gates)
        lengths = jnp.asarray(lengths, jnp.int32)
        idx = jnp.clip(lengths - 1, 0)[:, None, None]
        last = jnp.take_along_axis(x, idx, axis=1)
        last = L.norm(cfg, params["ln_f"], last)
        return L.unembed(cfg, params["embed"], last), pc

    def prefix_page_rows(self, history, share_len: int, page_size: int,
                         max_seq: int):
        """Shared COW page content: the first ``share_len`` (page-
        aligned) positions of each full-length history leaf as
        (lead..., n_shared, ps, KV, hd), batch squeezed — written to
        the pool once and block-mapped into every sharing row.  Ring/
        local leaves are never shared (each row's ring depends on its
        own total depth) and come back with zero pages."""
        local_len = self._ring_local_len(max_seq)

        def f(h, is_local):
            hh = h[..., 0, :share_len, :, :]
            if is_local:
                return jnp.zeros(hh.shape[:-3] + (0, page_size)
                                 + hh.shape[-2:], h.dtype)
            return _to_pages(hh, hh.ndim - 3, page_size)

        if "k" in history:
            return {k: f(history[k], False) for k in ("k", "v")}
        return {kn: {k: f(history[kn][k],
                          kn in ("inner", "tail") and local_len > 0)
                     for k in ("k", "v")}
                for kn in history}

    def suffix_page_rows(self, history, suffix_cache, lengths,
                         pre_len: int, share_len: int, page_size: int,
                         max_seq: int):
        """Per-row PRIVATE page content after a suffix prefill.

        Full-length leaves: pages covering absolute positions
        [share_len, pre_len + s_pad) — the re-materialized partial tail
        of the prefix plus the fresh suffix (share_len is page-aligned,
        so these pages start exactly after the shared COW pages and
        never alias them).  Ring/local leaves: each row's window ring
        at its own total depth, the same ``ring_kv_positions`` gather
        as the dense ``_pad_cache`` placement.  Returns a tree shaped
        like the cache kinds plus "pos" = pre_len + lengths."""
        local_len = self._ring_local_len(max_seq)
        lengths = jnp.asarray(lengths, jnp.int32)
        full_pos = pre_len + lengths

        def full_pages(h, sfx):
            rem = h[..., share_len:, :, :]
            rem = jnp.broadcast_to(rem, sfx.shape[:-3] + rem.shape[-3:])
            cat = jnp.concatenate([rem, sfx], axis=-3)
            return _to_pages(cat, cat.ndim - 3, page_size)

        def local_pages(h, sfx):
            hh = jnp.broadcast_to(h, sfx.shape[:-3] + h.shape[-3:])
            src = jnp.concatenate([hh, sfx], axis=-3)
            p = ATT.ring_kv_positions(full_pos - 1, local_len)   # (B, W)
            idx = jnp.clip(p, 0, src.shape[-3] - 1)
            shape = [1] * src.ndim
            shape[-4] = idx.shape[0]
            shape[-3] = local_len
            ring = jnp.take_along_axis(src, idx.reshape(shape),
                                       axis=src.ndim - 3)
            return _to_pages(ring, ring.ndim - 3, page_size)

        def kind_pages(hsub, ssub, is_local):
            fn = local_pages if is_local else full_pages
            return {k: fn(hsub[k], ssub[k]) for k in ("k", "v")}

        if "k" in suffix_cache:
            out = kind_pages(history, suffix_cache, False)
        else:
            out = {kn: kind_pages(history[kn], suffix_cache[kn],
                                  kn in ("inner", "tail") and local_len > 0)
                   for kn in suffix_cache}
        out["pos"] = full_pos
        return out

    def decode_step(self, params, cache, tokens, lora=None, gates=None,
                    absorb=False):
        """One-token decode.  tokens: (B,1).  Returns (logits, new_cache).

        Purely functional over the cache tree (every leaf of the input
        is either threaded through untouched or rebuilt by a scatter),
        so the serving engine can safely DONATE lane-cache buffers to a
        jitted step and run it inside a ``lax.scan`` macro-step: XLA
        updates the caches in place and no stale aliasing is possible.
        Parked rows (continuous batching: pos >= ATT.FREED_POS after
        EOS) keep decoding inside the scan as masked no-ops — their
        KV/ring scatters drop and ``pos`` freezes below."""
        cfg = self.cfg
        pos = cache["pos"]
        x = L.embed(cfg, params["embed"], tokens)
        if cfg.family == "audio":
            x = x + sinusoidal_at(pos, cfg.d_model, x.dtype)[None, None, :]
        pages = None
        if "block" in cache:
            # paged lane cache: KV leaves are page pools, "block"/"local"
            # are the per-row block tables (serving/paging.py)
            pages = {"block": cache["block"]}
            if "local" in cache:
                pages["local"] = cache["local"]
        x, nc, _ = self._run_stack(params, x, positions=pos, mode="decode",
                                   cache=cache, lora=lora, gates=gates,
                                   absorb=absorb, pages=pages)
        x = L.norm(cfg, params["ln_f"], x)
        logits = L.unembed(cfg, params["embed"], x)
        new_cache = dict(nc) if nc is not None else {}
        for k in cache:
            if k not in new_cache or new_cache.get(k) is None:
                new_cache[k] = cache[k]
        # parked rows (continuous batching: freed on EOS, pos set to
        # ATT.FREED_POS) hold position so "freed" stays an exact marker
        # and never creeps toward int32 overflow on long-idle lanes
        new_cache["pos"] = jnp.where(pos >= ATT.FREED_POS, pos, pos + 1)
        return logits, new_cache
