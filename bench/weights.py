"""Seeded weights of a Floe pair, drawn by the benchmark itself.

Every weight is a pure function of (seed, model, leaf, layer): a normal
draw from a threefry key folded from those four, times the leaf's
standard deviation, rounded to the type the weight is served in.  So the
serving program (which gets the whole tree in one jitted draw on its
devices) and the plain reference (which draws one layer at a time, in
float32 arithmetic on the same rounded values) see bit-identical
weights without either handing the other anything.

The layout here is the benchmark's own (``NATIVE_LEAVES``); ``model.py``
maps it onto the program's parameter tree.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

MODELS = {"slm": 0, "llm": 1, "align": 2}
# per-layer leaves, then whole-model leaves; the index is part of the key
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_in", "w_out")
MODEL_LEAVES = ("tok", "unembed", "ln_f")
ALIGN_LEAVES = ("w1", "b1", "w2", "b2")
ALIGN_HIDDEN = 64


def base_key(seed: int) -> jax.Array:
    """A key for any non-negative seed below 2**62 (``jax.random.key``
    keeps only the low 32 bits of a Python int)."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 62:
        raise ValueError(f"seed {seed} outside [0, 2**62)")
    return jax.random.fold_in(jax.random.key(seed % (1 << 31)),
                              seed >> 31)


def leaf_key(seed_key, model: str, leaf: str, layer: int = 0):
    names = LAYER_LEAVES + MODEL_LEAVES + ALIGN_LEAVES
    k = jax.random.fold_in(seed_key, MODELS[model])
    k = jax.random.fold_in(k, names.index(leaf))
    return jax.random.fold_in(k, layer)


def layer_shapes(m: dict) -> Dict[str, Tuple[int, ...]]:
    """Shapes of one decoder layer of model dims ``m`` (HF config keys)."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hd = d // m["num_attention_heads"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return {"ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
            "wo": (q, d), "ln2": (d,), "w_in": (d, 2 * f), "w_out": (f, d)}


def model_shapes(m: dict) -> Dict[str, Tuple[int, ...]]:
    d, v = m["hidden_size"], m["vocab_size"]
    return {"tok": (v, d), "unembed": (d, v), "ln_f": (d,)}


def align_shapes(vocab: int) -> Dict[str, Tuple[int, ...]]:
    return {"w1": (2 * vocab, ALIGN_HIDDEN), "b1": (ALIGN_HIDDEN,),
            "w2": (ALIGN_HIDDEN, 1), "b2": (1,)}


def _draw(key, leaf: str, shape, init: dict, dtype):
    """One leaf: norms are 1 + s*N(0,1); matrices N(0,1) * s/sqrt(fan_in)
    (``tok`` and the alignment MLP: N(0,1) * s, biases 0); the
    unembedding's ``eos_token_id`` column is zero."""
    s = init[leaf]
    z = jax.random.normal(key, shape, jnp.float32)
    if leaf in ("ln1", "ln2", "ln_f"):
        x = 1.0 + s * z
    elif leaf in ("tok", "w1", "w2"):
        x = s * z
    elif leaf in ("b1", "b2"):
        x = 0.0 * z
    else:
        x = z * (s / math.sqrt(shape[0]))
    if leaf == "unembed" and "eos_token_id" in init:
        # a zero column: the end-of-sequence logit is 0 against a largest
        # logit near 4 standard deviations, so no request ends before
        # its drawn length and every seed serves the same work
        x = x.at[:, int(init["eos_token_id"])].set(0.0)
    return x.astype(dtype)


def draw_layer(seed_key, model: str, m: dict, init: dict, layer,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """One decoder layer's leaves (``layer`` may be traced: vmap-able)."""
    return {n: _draw(leaf_key(seed_key, model, n, layer), n, sh, init,
                     dtype)
            for n, sh in layer_shapes(m).items()}


def draw_model_leaves(seed_key, model: str, m: dict, init: dict,
                      dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    return {n: _draw(leaf_key(seed_key, model, n), n, sh, init, dtype)
            for n, sh in model_shapes(m).items()}


def draw_stacked(seed_key, model: str, m: dict, init: dict,
                 dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Every leaf of a model, per-layer leaves stacked on a leading
    layer axis (the draw of layer l equals ``draw_layer(..., l)``)."""
    n = m["num_hidden_layers"]
    layers = jax.vmap(lambda l: draw_layer(seed_key, model, m, init, l,
                                           dtype))(jnp.arange(n))
    return dict(draw_model_leaves(seed_key, model, m, init, dtype),
                layers=layers)


def draw_align(seed_key, vocab: int, init: dict) -> Dict[str, jax.Array]:
    """The Eq. 14 alignment MLP, kept in float32 as the program keeps it."""
    return {n: _draw(leaf_key(seed_key, "align", n), n, sh, init,
                     jnp.float32)
            for n, sh in align_shapes(vocab).items()}
