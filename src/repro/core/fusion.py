"""Logit-level LLM-SLM alignment — paper Sec. IV-C (Eq. 12-15) + the
timeout fallback of Sec. IV-D.

Both models produce next-token distributions; a lightweight MLP maps the
concatenated distributions to a scalar fusion weight w ∈ [0,1]
(Eq. 14) and the output distribution is the convex combination (Eq. 15).
When the cloud logits miss the latency budget τ, w is forced to 1
(pure-SLM fallback).  All ops are jnp and jit-safe; the Pallas
``logit_fusion`` kernel fuses the two softmaxes + interpolation over
vocab blocks for the TPU target.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.models import layers as L


def alignment_spec(vocab: int, hidden: int = 64) -> Dict[str, L.P]:
    return {
        "w1": L.P((2 * vocab, hidden), ("vocab2", None), "fan_in"),
        "b1": L.P((hidden,), (None,), "zeros"),
        "w2": L.P((hidden, 1), (None, None), "fan_in"),
        "b2": L.P((1,), (None,), "zeros"),
    }


def init_alignment(key, vocab: int, hidden: int = 64, dtype=jnp.float32,
                   shardings=None):
    return L.materialize(alignment_spec(vocab, hidden), key, dtype,
                         shardings)


def fusion_weight(mlp, p_slm: jax.Array, p_llm: jax.Array) -> jax.Array:
    """Eq. 14: w = σ(MLP([P_SLM ; P_LLM])).  p_*: (B, V) probabilities."""
    h = jnp.concatenate([p_slm, p_llm], axis=-1).astype(jnp.float32)
    h = jnp.tanh(h @ mlp["w1"].astype(jnp.float32) + mlp["b1"])
    z = h @ mlp["w2"].astype(jnp.float32) + mlp["b2"]
    return jax.nn.sigmoid(z[..., 0])                  # (B,)


def fuse(p_slm: jax.Array, p_llm: jax.Array, w: jax.Array) -> jax.Array:
    """Eq. 15: P_out = w · P_SLM + (1-w) · P_LLM."""
    w = w[..., None]
    return w * p_slm + (1.0 - w) * p_llm


def fused_distribution(mlp, slm_logits: jax.Array, llm_logits: jax.Array,
                       llm_arrived: jax.Array | bool = True
                       ) -> Tuple[jax.Array, jax.Array]:
    """Full Sec. IV-C/IV-D step from raw logits.

    llm_arrived: scalar/per-batch bool — False forces w -> 1 (Sec. IV-D
    fallback: local SLM only).  Returns (P_out (B,V), w (B,))."""
    p_slm = jax.nn.softmax(slm_logits.astype(jnp.float32), axis=-1)
    p_llm = jax.nn.softmax(llm_logits.astype(jnp.float32), axis=-1)
    w = fusion_weight(mlp, p_slm, p_llm)
    arrived = jnp.asarray(llm_arrived)
    w = jnp.where(arrived, w, 1.0)
    return fuse(p_slm, p_llm, w), w


def fused_distribution_kernel(mlp, slm_logits: jax.Array,
                              llm_logits: jax.Array, arrived: jax.Array,
                              block_b: int = 8, mesh=None
                              ) -> Tuple[jax.Array, jax.Array]:
    """Batched Sec. IV-C/IV-D step routed through the Pallas kernel.

    The fusion weight w (Eq. 14) needs the two probability vectors as
    MLP input, so those softmaxes are computed here either way; the
    Eq. 15 output distribution is then produced by the ``logit_fusion``
    kernel, which re-derives both softmaxes tile by tile from the raw
    logits and per-row statistics instead of reading the (B, V)
    probability tensors back (whether that is faster on the chip is not
    measured).  arrived: (B,) bool; rows whose cloud logits missed τ get w=1
    (per-row fallback).  On a ``mesh`` the logit rows are replicated
    (the deployment's fusion contract) and every device runs the
    kernel on them: a Pallas TPU kernel is not partitioned
    automatically.  Returns (P_out (B,V), w (B,))."""
    from repro.kernels.logit_fusion.ops import fused_probs_masked
    p_slm = jax.nn.softmax(slm_logits.astype(jnp.float32), axis=-1)
    p_llm = jax.nn.softmax(llm_logits.astype(jnp.float32), axis=-1)
    w = fusion_weight(mlp, p_slm, p_llm)
    arrived = jnp.asarray(arrived, bool)
    fuse = partial(fused_probs_masked, block_b=block_b)
    if mesh is not None:
        rep = PartitionSpec()
        fuse = jax.shard_map(fuse, mesh=mesh, in_specs=(rep,) * 4,
                             out_specs=rep, check_vma=False)
    p = fuse(slm_logits, llm_logits, w, arrived)
    return p, jnp.where(arrived, w, 1.0)


# ---------------------------------------------------------------------------
# Alignment-MLP training (distillation-style: maximise log-prob of the
# reference next token under the fused distribution)
# ---------------------------------------------------------------------------


def alignment_loss(mlp, slm_logits, llm_logits, targets) -> jax.Array:
    p, _ = fused_distribution(mlp, slm_logits, llm_logits)
    logp = jnp.log(jnp.clip(p, 1e-9))
    nll = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return nll.mean()


@jax.jit
def _sgd(mlp, g, lr):
    return jax.tree.map(lambda p, gi: p - lr * gi, mlp, g)


def train_alignment(mlp, batches, lr: float = 1e-2, steps: int = 200):
    """batches: iterable of (slm_logits, llm_logits, targets)."""
    grad_fn = jax.jit(jax.value_and_grad(alignment_loss))
    losses = []
    it = iter(batches)
    cached = []
    for i in range(steps):
        try:
            b = next(it)
            cached.append(b)
        except StopIteration:
            b = cached[i % len(cached)]
        loss, g = grad_fn(mlp, *b)
        mlp = _sgd(mlp, g, jnp.asarray(lr))
        losses.append(float(loss))
    return mlp, losses
