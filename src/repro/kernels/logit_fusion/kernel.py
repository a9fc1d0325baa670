"""Fused logit-level LLM-SLM fusion Pallas kernel (Eq. 14-15 compute).

P_out = w·softmax(z_slm) + (1-w)·softmax(z_llm), tiled over the vocab.

A whole-vocab row does not fit VMEM at the pair's V = 256 000 (one
(8, V) f32 block is 8 MB per operand before double buffering), so the
softmax runs in two passes: the per-row max m and normaliser
s = Σ exp(z - m) of both models are reduced first (plain jnp, one read
of each logit row), then the kernel streams lane-aligned (bb, tv) vocab
tiles and writes w·exp(z_s - m_s)/s_s + (1-w)·exp(z_l - m_l)/s_l — the
same arithmetic as ``jax.nn.softmax``, elementwise per tile.  Row
blocks are multiples of 8 (the TPU sublane tiling); the serving
wrapper ``ops.fused_probs_masked`` pads ragged batches up to them.

The optional per-row ``arrived`` mask implements the Sec. IV-D timeout
fallback: rows whose cloud logits missed the τ budget get w forced to 1
(pure-SLM output).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# row-stat columns of the (B, 8) side input: fusion weight (arrived
# mask applied), then max / normaliser of the SLM and LLM rows
_W, _MS, _SS, _ML, _SL = range(5)


def _fusion_kernel(sl_ref, ll_ref, row_ref, o_ref):
    row = row_ref[...]                            # (bb, 8) f32
    w = row[:, _W:_W + 1]
    p_s = jnp.exp(sl_ref[...].astype(jnp.float32)
                  - row[:, _MS:_MS + 1]) / row[:, _SS:_SS + 1]
    p_l = jnp.exp(ll_ref[...].astype(jnp.float32)
                  - row[:, _ML:_ML + 1]) / row[:, _SL:_SL + 1]
    o_ref[...] = (w * p_s + (1.0 - w) * p_l).astype(o_ref.dtype)


def _vocab_block(v: int, cap: int = 16384) -> int:
    """Vocab tile width: the whole row when it fits ``cap``, else the
    widest multiple of 128 (the TPU lane width) <= cap dividing V, else
    the widest multiple of 128 <= cap (``fuse_logits`` then pads V)."""
    if v <= cap:
        return v
    top = cap - cap % 128
    for tv in range(top, 0, -128):
        if v % tv == 0:
            return tv
    return top


def _row_stats(x):
    """Per-row max and softmax normaliser, as ``jax.nn.softmax`` forms
    them: (B, 1) each."""
    x = x.astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    return m, jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True)


def fuse_logits(slm_logits, llm_logits, w, *, arrived=None, block_b: int = 8,
                block_v: int = 16384, interpret: bool = False):
    """slm/llm logits: (B, V); w: (B,); arrived: optional (B,) bool —
    rows with arrived=False are forced to w=1.  -> fused probs (B, V).
    B must be a multiple of ``block_b`` or smaller than it."""
    b, v = slm_logits.shape
    bb = min(block_b, b)
    assert b % bb == 0, (b, bb)
    tv = _vocab_block(v, block_v)
    w = w.reshape(b, 1).astype(jnp.float32)
    if arrived is not None:
        w = jnp.where(arrived.reshape(b, 1), w, 1.0)
    m_s, s_s = _row_stats(slm_logits)
    m_l, s_l = _row_stats(llm_logits)
    row = jnp.concatenate([w, m_s, s_s, m_l, s_l,
                           jnp.zeros((b, 3), jnp.float32)], axis=1)
    vp = -(-v // tv) * tv
    if vp != v:       # -inf logits fuse to probability 0, sliced away
        pad = ((0, 0), (0, vp - v))
        slm_logits = jnp.pad(slm_logits, pad, constant_values=-jnp.inf)
        llm_logits = jnp.pad(llm_logits, pad, constant_values=-jnp.inf)
    tile = pl.BlockSpec((bb, tv), lambda i, j: (i, j))
    out = pl.pallas_call(
        _fusion_kernel,
        grid=(b // bb, vp // tv),
        in_specs=[tile, tile, pl.BlockSpec((bb, 8), lambda i, j: (i, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b, vp), jnp.float32),
        interpret=interpret,
    )(slm_logits, llm_logits, row)
    return out[:, :v]
