"""Jit'd wrappers for the fused logit-fusion kernel.

``fused_probs`` is the raw fixed-shape dispatch; ``fused_probs_masked``
is the serving entry point: it pads a ragged decode batch up to a
``block_b`` multiple (padded rows are masked out and sliced away) and
threads the per-row Sec. IV-D ``arrived`` fallback mask into the kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.logit_fusion.kernel import fuse_logits


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("block_b",))
def fused_probs(slm_logits, llm_logits, w, block_b: int = 8):
    return fuse_logits(slm_logits, llm_logits, w, block_b=block_b,
                       interpret=_on_cpu())


@partial(jax.jit, static_argnames=("block_b",))
def fused_probs_masked(slm_logits, llm_logits, w, arrived,
                       block_b: int = 8):
    """Ragged-batch serving dispatch.

    slm/llm logits: (B, V) for any B >= 1; w: (B,); arrived: (B,) bool.
    B is padded up to a multiple of ``block_b`` (padded rows carry
    arrived=False and are dropped after the kernel), so the continuous-
    decode engine can hand over whatever batch occupancy it has and the
    kernel always sees row blocks that are multiples of the TPU's
    8-row sublane tiling."""
    b, _ = slm_logits.shape
    bp = -(-b // block_b) * block_b
    pad = bp - b
    if pad:
        zrow = ((0, pad), (0, 0))
        slm_logits = jnp.pad(slm_logits, zrow)
        llm_logits = jnp.pad(llm_logits, zrow)
        w = jnp.pad(w.astype(jnp.float32), (0, pad), constant_values=1.0)
        arrived = jnp.pad(jnp.asarray(arrived, bool), (0, pad),
                          constant_values=False)
    out = fuse_logits(slm_logits, llm_logits, w, arrived=arrived,
                      block_b=block_b, interpret=_on_cpu())
    return out[:b]


def cloud_arrival_mask(ok, active, lost=None, outage=None, degraded=None):
    """The Sec. IV-D fallback mask, extended for the fault-injected
    link: a row's cloud logits take part in the fusion iff the reply
    arrived within the timeout AND the row is active AND the reply was
    not lost AND the link is not in an outage window AND the row's
    circuit breaker is not holding it in SLM-only degraded mode.

    Pure elementwise boolean algebra — works on numpy arrays (the
    per-token host path) and on traced jnp arrays (the macro scan)
    alike, so every path builds the mask with the same expression.
    ``None`` fault terms are skipped, which keeps the fault-free oracle
    mask literally ``ok & active``."""
    m = ok & active
    if lost is not None:
        m = m & ~lost
    if outage is not None:
        m = m & ~outage
    if degraded is not None:
        m = m & ~degraded
    return m


def accept_prefix(draft, sel, steps, max_new, active, eos: int):
    """Fused accept/rollback epilogue of a speculative draft/verify
    burst (tentpole PR 10): accept the longest draft prefix the fused
    distribution agrees with, then cap it by EOS and the per-row token
    budget.

    draft, sel: (k, B) int32 — the SLM's k greedy draft tokens and the
    fused distribution's per-position choices (greedy argmax or the
    keyed sample; along the accepted prefix both paths see bitwise the
    baseline per-token distributions, so agreement there IS baseline
    equivalence).  steps/max_new: (B,) int32 emitted-so-far / budget;
    active: (B,) bool.

    Returns (n_emit, c_sel, done_now, correction):
      n_emit     (B,) tokens emitted this burst (0 for inactive rows;
                 the emitted tokens are sel[:n_emit]),
      c_sel      (B,) length of the agreeing prefix (sel == draft),
      done_now   (B,) row finished (EOS emitted or budget exhausted),
      correction (B,) row's last emitted token diverged from the draft
                 (the "+1" bonus token) — its SLM state needs one
                 post-rollback decode of sel[n_emit-1].

    Invariant: an active row with neither done_now nor correction
    accepted the full window (n_emit == k <= c_sel).  Pure elementwise
    jnp — traceable inside the burst jit and checked against
    ``ref.accept_prefix_ref``."""
    k = draft.shape[0]
    match = (sel == draft)
    c_sel = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=0), axis=0)
    n_raw = jnp.minimum(c_sel + 1, k)
    idx = jnp.arange(k, dtype=jnp.int32)[:, None]
    is_eos = (sel == eos) & (idx < n_raw[None, :])
    eos_pos = jnp.min(jnp.where(is_eos, idx, k), axis=0)
    n1 = jnp.minimum(n_raw, eos_pos + 1)
    rem = max_new - steps
    n_emit = jnp.maximum(jnp.minimum(n1, rem), 1)
    last = jnp.take_along_axis(sel, (n_emit - 1)[None, :], axis=0)[0]
    done_now = active & ((last == eos) | (steps + n_emit >= max_new))
    correction = active & ~done_now & (n_emit == c_sel + 1)
    n_emit = jnp.where(active, n_emit, 0)
    return n_emit, c_sel, done_now, correction


def _categorical_rows(probs, rids, steps, seed: int):
    """Vmapped keyed categorical: row i draws with key
    fold_in(fold_in(key(seed), rids[i]), steps[i])."""
    def one(p, r, s):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(seed), r), s)
        return jax.random.categorical(key, jnp.log(jnp.clip(p, 1e-9)))
    return jax.vmap(one)(probs, jnp.asarray(rids, jnp.int32),
                         jnp.asarray(steps, jnp.int32))


@partial(jax.jit, static_argnames=("seed",))
def sample_fused(probs, rids, steps, seed: int = 0):
    """On-device batched sampling from the fused distribution.

    Replaces the serving engine's per-row host loop with one vmapped
    categorical: row i draws with key fold_in(fold_in(key(seed),
    rids[i]), steps[i]) — bit-identical to the sequential engine's
    per-(request, token) stream, so batched and sequential serving see
    the same samples, and distinct rows never share a key.

    probs: (B, V) fused distribution; rids/steps: (B,) int32.
    Returns (B,) sampled token ids."""
    return _categorical_rows(probs, rids, steps, seed)


@partial(jax.jit, static_argnames=("seed", "sample"))
def select_sample_fused(probs, greedy, rids, steps, seed: int = 0,
                        sample: bool = True):
    """Fused next-token epilogue of the decode macro-step: per-row
    greedy argmax OR keyed categorical, selected by the (B,) ``greedy``
    mask, in one dispatch.  The categorical keys are exactly
    ``sample_fused``'s (fold_in(fold_in(key(seed), rids[i]), steps[i])),
    so mixed greedy/sampled batches stay bit-identical to the per-path
    ops.  ``sample=False`` (static) skips the categorical entirely —
    all-greedy lanes never pay the (B, V) Gumbel draw.

    probs: (B, V); greedy: (B,) bool; rids/steps: (B,) int32.
    Returns (B,) int32 token ids."""
    nxt = jnp.argmax(probs, axis=-1).astype(jnp.int32)
    if not sample:
        return nxt
    drawn = _categorical_rows(probs, rids, steps, seed).astype(jnp.int32)
    return jnp.where(jnp.asarray(greedy, bool), nxt, drawn)
