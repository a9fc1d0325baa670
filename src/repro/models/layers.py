"""Core layer primitives + declarative parameter-spec system.

Every module declares its parameters as a tree of ``P`` specs (shape +
logical axis names + init).  ``materialize`` turns a spec tree into real
arrays; ``axes_tree`` yields the parallel tree of logical-axis tuples that
``launch/sharding.py`` maps onto the mesh with divisibility fallbacks.

Weights are kept 2-D ``(in, out)`` wherever possible (head structure via
reshape at the call site) so one sharding rule covers every projection.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Tree = Any


@dataclass(frozen=True)
class P:
    """Parameter spec: shape, logical axes (one name per dim), init."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "fan_in"        # fan_in | normal | zeros | ones | embed | small
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_array(spec: P, key, dtype) -> jax.Array:
    shape = spec.shape
    if spec.init == "zeros":
        return jnp.zeros(shape, dtype)
    if spec.init == "ones":
        return jnp.ones(shape, dtype)
    if spec.init == "embed":
        return (jax.random.normal(key, shape, jnp.float32) * spec.scale).astype(dtype)
    if spec.init == "normal":
        return (jax.random.normal(key, shape, jnp.float32) * 0.02 * spec.scale).astype(dtype)
    if spec.init == "small":
        return (jax.random.normal(key, shape, jnp.float32) * 1e-3 * spec.scale).astype(dtype)
    # fan_in: LeCun/Kaiming-style — fan-in = product of all dims except last
    fan_in = max(1, math.prod(shape[:-1]))
    std = spec.scale / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.lru_cache(maxsize=None)
def _materializer(treedef, leaves: Tuple[P, ...], dtype, shardings):
    def build(key):
        keys = jax.random.split(key, max(1, len(leaves)))
        return jax.tree.unflatten(
            treedef, [_init_array(s, k, dtype) for s, k in zip(leaves, keys)])
    out = (None if shardings is None
           else jax.tree.unflatten(treedef, list(shardings)))
    return jax.jit(build, out_shardings=out)


def materialize(specs: Tree, key: jax.Array, dtype,
                shardings: Optional[Tree] = None) -> Tree:
    """Spec tree -> params tree (single traversal, split keys per leaf).

    The draws run as one jitted program, so each leaf's f32 draw, scale
    and cast fuse and only the ``dtype`` result is ever stored.
    ``shardings`` (a tree of shardings matching ``specs``) makes every
    leaf come out already laid out on its devices: no whole leaf, and no
    whole model, passes through one device on the way."""
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    flat_sh = None
    if shardings is not None:
        flat_sh = tuple(treedef.flatten_up_to(shardings))
    return _materializer(treedef, tuple(leaves), jnp.dtype(dtype),
                         flat_sh)(key)


def axes_tree(specs: Tree) -> Tree:
    return jax.tree.map(lambda s: s.axes, specs,
                        is_leaf=lambda x: isinstance(x, P))


def abstract_params(specs: Tree, dtype) -> Tree:
    """ShapeDtypeStructs for dry-run lowering (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_spec(d: int) -> Dict[str, P]:
    return {"scale": P((d,), ("d_model",), "ones")}


def rmsnorm(p, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


def layernorm_spec(d: int) -> Dict[str, P]:
    return {"scale": P((d,), ("d_model",), "ones"),
            "bias": P((d,), ("d_model",), "zeros")}


def layernorm(p, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(dt)


def norm_spec(cfg, d=None):
    d = d or cfg.d_model
    return layernorm_spec(d) if cfg.norm_type == "layernorm" else rmsnorm_spec(d)


def norm(cfg, p, x):
    if cfg.norm_type == "layernorm":
        return layernorm(p, x, cfg.norm_eps)
    return rmsnorm(p, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Linear (+ optional merged multi-LoRA delta — Floe Eq. 8)
# ---------------------------------------------------------------------------


def linear_spec(d_in: int, d_out: int, in_ax: str, out_ax: str,
                bias: bool = False, init: str = "fan_in",
                scale: float = 1.0) -> Dict[str, P]:
    s = {"w": P((d_in, d_out), (in_ax, out_ax), init, scale)}
    if bias:
        s["b"] = P((d_out,), (out_ax,), "zeros")
    return s


def linear(p, x, lora: Optional[Dict[str, jax.Array]] = None,
           gates: Optional[jax.Array] = None):
    """y = x @ W (+ b) (+ Σ_j ω_j · x A_jᵀ B_jᵀ  — the Floe merged-LoRA delta).

    lora: {"A": (E, r, d_in), "B": (E, d_out, r)}  (rank-padded; see
    core/lora.py), gates: (E,) router weights ω from core/router.py.
    """
    w = p["w"]
    y = jnp.einsum("...k,kn->...n", x, w,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    if lora is not None:
        y = y + lora_delta(lora, x, gates).astype(y.dtype)
    return y


def lora_delta(lora: Dict[str, jax.Array], x: jax.Array,
               gates: Optional[jax.Array]) -> jax.Array:
    """Σ_j ω_j B_j A_j x  (paper Eq. 8).  A: (E, r, k); B: (E, n, r).

    ``gates`` is normally a float gate matrix — (B, E) per-request
    weights or (E,) global weights.  A 1-D INTEGER ``gates`` is the
    slot-decode fast path: per-row adapter slot ids (negative = no
    adapter), routed through the scalar-prefetch
    ``moe_lora_delta_slots`` kernel, which gathers exactly one expert
    per row instead of sweeping the dense Σ over E — the serving
    engines' ``use_slot_kernel`` decode hot path.  Adaptive-rank banks
    (``rank_mask``) fall back to the dense path through an equivalent
    one-hot matrix (the mask multiplies the rank axis, which the slot
    kernel does not thread)."""
    A, B = lora["A"], lora["B"]
    if gates is not None and gates.ndim == 1 \
            and jnp.issubdtype(gates.dtype, jnp.integer):
        if "rank_mask" in lora:
            gates = jax.nn.one_hot(jnp.clip(gates, 0, A.shape[0] - 1),
                                   A.shape[0], dtype=jnp.float32
                                   ) * (gates >= 0)[:, None]
        else:
            from repro.kernels.moe_lora.kernel import moe_lora_delta_slots
            lead = x.shape[:-1]
            xf = x.reshape(-1, x.shape[-1])
            slots = jnp.broadcast_to(
                gates.reshape(gates.shape[0],
                              *([1] * (len(lead) - 1))), lead
            ).reshape(-1)
            delta = moe_lora_delta_slots(
                xf, A, B, slots,
                interpret=jax.default_backend() == "cpu")
            return delta.reshape(*lead, B.shape[1]).astype(jnp.float32)
    u = jnp.einsum("...k,erk->...er", x, A,
                   preferred_element_type=jnp.float32)
    if "rank_mask" in lora:            # adaptive-rank compression Q_r (Thm. 1)
        u = u * lora["rank_mask"].astype(u.dtype)
    if gates is not None:
        g = gates.astype(u.dtype)
        if g.ndim == 2:                # per-request gates ω: (B, E)
            g = g.reshape(g.shape[0], *([1] * (u.ndim - 3)), g.shape[1], 1)
        else:                          # global gates: (E,)
            g = g[:, None]
        u = u * g
    y = jnp.einsum("...er,enr->...n", u, B.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return y


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq       # (..., S, half)
    sin = jnp.sin(ang)[..., None, :]                            # (..., S, 1, half)
    cos = jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs (SwiGLU / GeGLU / GELU)
# ---------------------------------------------------------------------------


def mlp_spec(cfg, d_ff: Optional[int] = None) -> Dict[str, Any]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "in": linear_spec(d, 2 * f, "d_model", "d_ff_gated"),
            "out": linear_spec(f, d, "d_ff", "d_model"),
        }
    return {
        "in": linear_spec(d, f, "d_model", "d_ff"),
        "out": linear_spec(f, d, "d_ff", "d_model"),
    }


def mlp(cfg, p, x, lora_in=None, lora_out=None, gates=None):
    h = linear(p["in"], x, lora_in, gates)
    if cfg.mlp_type in ("swiglu", "geglu"):
        g, u = jnp.split(h, 2, axis=-1)
        act = jax.nn.silu(g) if cfg.mlp_type == "swiglu" else jax.nn.gelu(g)
        h = act * u
    else:
        h = jax.nn.gelu(h)
    return linear(p["out"], h, lora_out, gates)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_spec(cfg) -> Dict[str, Any]:
    s = {"tok": {"w": P((cfg.vocab_size, cfg.d_model), ("vocab", "d_model"),
                        "embed", cfg.d_model ** -0.5)}}
    if not cfg.tie_embeddings:
        s["unembed"] = linear_spec(cfg.d_model, cfg.vocab_size,
                                   "d_model", "vocab")
    return s


def embed(cfg, p, tokens):
    x = jnp.take(p["tok"]["w"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(cfg, p, x):
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x, p["tok"]["w"],
                          preferred_element_type=jnp.float32)
    return jnp.einsum("...d,dv->...v", x, p["unembed"]["w"],
                      preferred_element_type=jnp.float32)
