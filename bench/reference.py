"""Plain float32 reference of the Phi-3 dense decoder and the Floe fusion.

Written from the published descriptions (Phi-3: arXiv:2404.14219 and
its Hugging Face ``Phi3ForCausalLM``; Floe: Eq. 14-15) in
straightforward ``jax.numpy``: no cache, no kernels, no batching of
requests, every matmul at ``highest`` precision.  It imports nothing of
the program under test and takes nothing the program made: weights are
redrawn here from the seed, layer by layer (``weights.draw_layer``), so
only one layer's float32 weights are live at a time.

Block (pre-norm): x += Wo·attn(rope(Wq·n1(x)), rope(Wk·n1(x)), Wv·n1(x));
x += Wout·(silu(g) * u) with [g; u] = Win·n2(x); n = RMSNorm(eps) * scale.
Attention is causal over the whole context: every context the benchmark
serves is shorter than Phi-3's 2 047-token sliding window, so the window
masks nothing.  RoPE rotates the two halves of each head (rotate_half),
theta = ``rope_theta``.

``control=True`` computes the same thing with every matmul operand
rounded to float8 e4m3 (absmax-scaled per row of the contraction): the
nearest precision below the bfloat16 the configuration serves in.  It
exists to show that the comparison in ``check.py`` tells such a program
apart from a sound one.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

F8_MAX = 448.0          # largest finite float8_e4m3fn


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, control):
    """a (..., k) @ b (k, n)."""
    if control:
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision="highest")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (T, heads, hd) at positions 0..T-1."""
    t, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta",
                                   "control"))
def block(x, w, *, heads, kv_heads, eps, theta, control):
    """One decoder layer on one sequence x: (T, d) float32."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    t, d = x.shape
    hd = w["wq"].shape[1] // heads
    h = rmsnorm(x, w["ln1"], eps)
    q = rope(_mm(h, w["wq"], control).reshape(t, heads, hd), theta)
    k = rope(_mm(h, w["wk"], control).reshape(t, kv_heads, hd), theta)
    v = _mm(h, w["wv"], control).reshape(t, kv_heads, hd)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    if control:
        q, k = _q8(q, -1), _q8(k, -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if control:
        p, v = _q8(p, -1), _q8(v, 0)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision="highest")
    x = x + _mm(o.reshape(t, heads * hd), w["wo"], control)
    h = rmsnorm(x, w["ln2"], eps)
    g, u = jnp.split(_mm(h, w["w_in"], control), 2, axis=-1)
    return x + _mm(jax.nn.silu(g) * u, w["w_out"], control)


@partial(jax.jit, static_argnames=("eps", "control"))
def head(x, ln_f, unembed, *, eps, control):
    """Final norm and unembedding of selected positions x: (n, d)."""
    h = rmsnorm(x, ln_f.astype(jnp.float32), eps)
    return _mm(h, unembed.astype(jnp.float32), control)


_draw_layer = jax.jit(W.draw_layer, static_argnames=("model", "m", "init"))
_draw_model = jax.jit(W.draw_model_leaves,
                      static_argnames=("model", "m", "init"))


class _Frozen(dict):
    """A hashable dict, so model dims can be static jit arguments."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def frozen(d: dict) -> _Frozen:
    return _Frozen({k: v for k, v in d.items()
                    if isinstance(v, (int, float, str, bool))})


def logits_at(seed_key, model: str, m: dict, init: dict,
              seqs: Sequence[Sequence[int]], want: Sequence[Sequence[int]],
              control: bool = False, pad_to: int = 256,
              pos_pad: int = 256) -> List[np.ndarray]:
    """Reference logits of ``model`` at positions ``want[i]`` of token
    sequence ``seqs[i]`` (position p predicts token p+1).  Sequences are
    padded at the end to a multiple of ``pad_to`` and position lists to
    one of ``pos_pad`` (causality keeps padding out of every valid
    position; fixed sizes keep the compiled programs few).  Returns one
    (padded positions, vocab) float32 device array per sequence, whose
    first len(want[i]) rows are the logits asked for."""
    m, init = frozen(m), frozen(init)
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    top = _draw_model(seed_key, model, m, init)
    xs = []
    for s in seqs:
        t = -(-len(s) // pad_to) * pad_to
        ids = np.zeros((t,), np.int32)
        ids[:len(s)] = s
        xs.append(top["tok"].astype(jnp.float32)[jnp.asarray(ids)])
    for layer in range(m["num_hidden_layers"]):
        w = _draw_layer(seed_key, model, m, init, layer)
        xs = [block(x, w, heads=heads, kv_heads=kv, eps=eps, theta=theta,
                    control=control) for x in xs]
        del w
    out = []
    for x, pos in zip(xs, want):
        idx = np.zeros((-(-len(pos) // pos_pad) * pos_pad,), np.int32)
        idx[:len(pos)] = pos
        out.append(head(x[jnp.asarray(idx)], top["ln_f"], top["unembed"],
                        eps=eps, control=control))
    return out


def fusion_weight(align: Dict[str, jax.Array], p_slm, p_llm):
    """Floe Eq. 14: w = sigmoid(W2 tanh(W1 [p_slm; p_llm] + b1) + b2)."""
    h = jnp.concatenate([p_slm, p_llm], -1)
    h = jnp.tanh(jnp.matmul(h, align["w1"], precision="highest")
                 + align["b1"])
    z = jnp.matmul(h, align["w2"], precision="highest") + align["b2"]
    return jax.nn.sigmoid(z[..., 0])


@jax.jit
def fused_logprobs(align, z_slm, z_llm):
    """Floe Eq. 15 in log space: log(w p_slm + (1 - w) p_llm)."""
    p_s = jax.nn.softmax(z_slm, -1)
    p_l = jax.nn.softmax(z_llm, -1)
    w = fusion_weight(align, p_s, p_l)[..., None]
    return jnp.log(w * p_s + (1.0 - w) * p_l)


@jax.jit
def slm_logprobs(z_slm):
    return jax.nn.log_softmax(z_slm, -1)
