"""Attention: GQA with full-causal, sliding-window and decode paths.

Prefill/train attention is *query-chunked* with static KV slices per chunk
(Python loop over chunks -> static shapes, exact-causal FLOPs, O(chunk·S)
peak memory instead of O(S²)).  Sliding-window layers slice only the
window neighbourhood, giving honest O(S·w) FLOPs for long contexts.
Decode attends one token against the cache (optionally window-sliced).

The Pallas flash-attention kernel in ``repro/kernels/flash_attention`` is
the TPU-target implementation of the same math; this module is the jnp
path used for CPU tests and dry-run lowering.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.sharding_hooks import constrain

NEG_INF = -2.0 ** 30

# Continuous batching: a freed (EOS-drained) batch row is "parked" at
# this position until re-admission.  Both rowwise decode scatter paths
# drop cache writes for parked rows (the plain path because FREED_POS is
# far past max_seq, the ring path via an out-of-range slot index), so a
# drained row's cache stays bit-identical while it idles in the batch —
# including across the iterations of the serving engine's on-device
# macro-step scan, where rows that hit EOS mid-macro park themselves
# via a mask (no host involvement) and keep "decoding" as no-ops until
# the next admission boundary.  Far below int32 max so pos+1 per idle
# step never overflows.
FREED_POS = 1 << 30


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def attn_spec(cfg) -> Dict[str, Any]:
    d = cfg.d_model
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = {
        "q": L.linear_spec(d, h * hd, "d_model", "heads_hd", bias=cfg.qkv_bias),
        "k": L.linear_spec(d, kv * hd, "d_model", "kv_hd", bias=cfg.qkv_bias),
        "v": L.linear_spec(d, kv * hd, "d_model", "kv_hd", bias=cfg.qkv_bias),
        "o": L.linear_spec(h * hd, d, "heads_hd", "d_model"),
    }
    if cfg.use_qk_norm:
        s["q_norm"] = {"scale": L.P((hd,), ("head_dim",), "ones")}
        s["k_norm"] = {"scale": L.P((hd,), ("head_dim",), "ones")}
    return s


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, mask, scale):
    """q: (B,Sq,KV,G,hd)  k/v: (B,Sk,KV,hd)  mask: (B?,Sq,Sk) bool."""
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def _group(q, num_kv):
    b, s, hhd = q.shape[0], q.shape[1], q.shape[2] * q.shape[3]
    h = q.shape[2]
    g = h // num_kv
    return q.reshape(b, s, num_kv, g, q.shape[3])


def chunked_causal_attention(q, k, v, q_pos, kv_pos, window: int = 0,
                             chunk: int = 1024):
    """Exact causal (optionally sliding-window) attention.

    q: (B, S, H, hd); k/v: (B, S, KV, hd); q_pos/kv_pos: (S,) absolute.
    Returns (B, S, H, hd).
    """
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    qg = _group(q, kvh)                                    # (B,S,KV,G,hd)
    if s <= chunk:
        mask = kv_pos[None, None, :] <= q_pos[None, :, None]
        if window:
            mask &= kv_pos[None, None, :] > q_pos[None, :, None] - window
        out = _sdpa(qg, k, v, mask, scale)
        return out.reshape(b, s, h, hd)

    n_chunks = -(-s // chunk)
    outs = []
    for i in range(n_chunks):
        lo, hi = i * chunk, min((i + 1) * chunk, s)
        qc = qg[:, lo:hi]
        qp = q_pos[lo:hi]
        if window:
            # only the window neighbourhood can be visible
            k_lo = max(0, hi - chunk - window)
        else:
            k_lo = 0
        kc, vc = k[:, k_lo:hi], v[:, k_lo:hi]
        kp = kv_pos[k_lo:hi]
        mask = kp[None, None, :] <= qp[None, :, None]
        if window:
            mask &= kp[None, None, :] > qp[None, :, None] - window
        outs.append(_sdpa(qc, kc, vc, mask, scale).reshape(b, hi - lo, h, hd))
    return jnp.concatenate(outs, axis=1)


def bidirectional_attention(q, k, v):
    """Whisper encoder / cross attention (no mask)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    qg = _group(q, kvh)
    mask = jnp.ones((1, s, k.shape[1]), bool)
    return _sdpa(qg, k, v, mask, 1.0 / math.sqrt(hd)).reshape(b, s, h, hd)


def decode_attention(q, cache_k, cache_v, pos, window: int = 0):
    """One-token decode: q (B,1,H,hd), cache (B,S,KV,hd), pos scalar."""
    b, _, h, hd = q.shape
    s_max = cache_k.shape[1]
    kvh = cache_k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    if window and window < s_max:
        start = jnp.clip(pos + 1 - window, 0, s_max - window)
        k = jax.lax.dynamic_slice_in_dim(cache_k, start, window, axis=1)
        v = jax.lax.dynamic_slice_in_dim(cache_v, start, window, axis=1)
        kv_pos = start + jnp.arange(window)
    else:
        k, v, kv_pos = cache_k, cache_v, jnp.arange(s_max)
    qg = _group(q, kvh)
    mask = (kv_pos <= pos)[None, None, :]
    return _sdpa(qg, k, v, mask, scale).reshape(b, 1, h, hd)


def rowwise_decode_attention(q, cache_k, cache_v, pos_b, window: int = 0):
    """One-token decode with PER-ROW positions (continuous batching: each
    slot is at its own depth).  q (B,1,H,hd), cache (B,S,KV,hd),
    pos_b (B,) int32.  Window layers keep the full cache and mask the
    neighbourhood instead of slicing (per-row starts preclude one static
    slice)."""
    b, _, h, hd = q.shape
    s_max = cache_k.shape[1]
    kvh = cache_k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    kv_pos = jnp.arange(s_max)
    mask = kv_pos[None, None, :] <= pos_b[:, None, None]      # (B,1,S)
    if window and window < s_max:
        mask &= kv_pos[None, None, :] > (pos_b[:, None, None] - window)
    qg = _group(q, kvh)
    return _sdpa(qg, cache_k, cache_v, mask, scale).reshape(b, 1, h, hd)


# ---------------------------------------------------------------------------
# Paged KV layout (serving/paging.py owns the host-side allocator)
# ---------------------------------------------------------------------------
#
# A paged lane cache replaces each (*L, B, S, KV, hd) leaf (*L: its
# stack's layer axes) with a pool (*L, P, page_size, KV, hd) plus a
# per-row block table (B, n_pages): the value at row position p of
# layer l lives at page table[b, p // page_size], offset p % page_size,
# of pool[l].  Decode carries the
# whole pool through the layer scan (models/model.py ``scan_layers``):
# each layer, told its index, writes its one new token per row in place
# with one scatter at [layer, page, offset], so no pool-sized copy is
# rebuilt per token.  It then reads its layer's slice, gathers each
# row's mapped pages back into the dense rowwise layout and runs the
# IDENTICAL attention math, so paged decode is bit-for-bit the dense
# path: extra gathered slots are masked, masked scores hit NEG_INF, and
# exp underflows to exact 0.0 in f32 — adding exact zeros never
# perturbs the reduction.  Unmapped table entries hold a sentinel far
# past the pool so writes drop (mode="drop") and gathers clamp onto
# masked garbage.


def gather_pages(pool_flat, table, n_slots: int, page_size: int):
    """Dense per-row view of a paged pool.

    pool_flat: (P*page_size, ...) slot-flattened pool; table: (B,
    n_pages) int32.  Returns (B, n_slots, ...): row b, slot j =
    pool[table[b, j//page_size], j%page_size].  Sentinel/garbage pages
    clamp into range; callers mask those slots out."""
    n_pool = pool_flat.shape[0] // page_size
    j = jnp.arange(n_slots)
    pid = jnp.take(table, j // page_size, axis=1)            # (B, n)
    flat = jnp.clip(pid, 0, n_pool - 1) * page_size \
        + (j % page_size)[None, :]
    return jnp.take(pool_flat, flat, axis=0, mode="clip")


def scatter_page_token(pool, table, row_pos, slot, token_kv,
                       slot_limit: int, layer):
    """Write one decode token per row into its mapped page.

    pool: (*L, P, page_size, ...) with one leading layer axis per entry
    of ``layer``, the tuple of scalars that indexes the written layer;
    table: (B, n_pages); slot: (B,) in-row slot index (absolute position
    for full-length leaves, pos % window for ring leaves); token_kv:
    (B, ...).  Parked rows (row_pos >= FREED_POS), slots past
    ``slot_limit`` (mirrors the dense scatter dropping row_pos >=
    max_seq), and unmapped NO_PAGE entries all produce an out-of-pool
    flat index, so the write drops instead of corrupting a live page."""
    n = len(layer)
    p_pages, ps = pool.shape[n], pool.shape[n + 1]
    page_ix = jnp.minimum(slot // ps, table.shape[1] - 1)
    pid = jnp.take_along_axis(table, page_ix[:, None], axis=1)[:, 0]
    ok = (row_pos < FREED_POS) & (slot < slot_limit)
    flat = jnp.where(ok, pid * ps + slot % ps, p_pages * ps)
    flat_pool = pool.reshape(pool.shape[:n] + (p_pages * ps,)
                             + pool.shape[n + 2:])
    out = flat_pool.at[layer + (flat,)].set(token_kv, mode="drop")
    return out.reshape(pool.shape)


def ring_kv_positions(pos, window: int) -> jax.Array:
    """Absolute position held by each slot of a ring cache at depth
    ``pos``: slot i holds p = pos - ((pos - i) mod window), i.e. the
    most recent position <= pos that maps to slot i (= p % window).
    p < 0 marks a slot not yet written.  pos scalar -> (window,);
    pos (B,) -> (B, window).  The single source of the ring addressing
    invariant — decode writes, decode masks, and prefill cache
    placement (LM._pad_cache) must all agree with it."""
    pos = jnp.asarray(pos)[..., None]
    slots = jnp.arange(window)
    return pos - jnp.mod(pos - slots, window)


def ring_decode_attention(q, cache_k, cache_v, pos, window: int):
    """Decode against a ring-buffered window cache (B, window, KV, hd).

    The mask keeps slot positions in [max(0, pos-window+1), pos]."""
    b, _, h, hd = q.shape
    kvh = cache_k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    kv_pos = ring_kv_positions(pos, window)
    mask = ((kv_pos >= 0) & (kv_pos <= pos))[None, None, :]
    qg = _group(q, kvh)
    return _sdpa(qg, cache_k, cache_v, mask, scale).reshape(b, 1, h, hd)


def rowwise_ring_decode_attention(q, cache_k, cache_v, pos_b, window: int):
    """Ring-buffer decode with PER-ROW positions (continuous batching over
    sliding-window layers: each batch row sits at its own depth AND its
    own ring write index).  q (B,1,H,hd), cache (B,window,KV,hd),
    pos_b (B,) int32.

    Per row, the mask keeps slot positions in
    [max(0, pos_b[b]-window+1), pos_b[b]], so rows that have not wrapped
    yet (pos < window) simply mask their empty slots."""
    b, _, h, hd = q.shape
    kvh = cache_k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    kv_pos = ring_kv_positions(pos_b, window)                   # (B, W)
    mask = ((kv_pos >= 0) & (kv_pos <= pos_b[:, None]))[:, None, :]
    qg = _group(q, kvh)
    return _sdpa(qg, cache_k, cache_v, mask, scale).reshape(b, 1, h, hd)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + cache plumbing + LoRA hooks)
# ---------------------------------------------------------------------------


def _qk_norm(p, x, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


def attention_block(cfg, p, x, *, positions, lora=None, gates=None,
                    is_global: bool = True,
                    cache: Optional[Dict[str, jax.Array]] = None,
                    mode: str = "train",
                    rope_enabled: bool = True,
                    pages: Optional[Dict[str, jax.Array]] = None,
                    ) -> Tuple[jax.Array, Optional[Dict]]:
    """Full attention sub-layer.  Returns (output, new_cache_or_None).

    mode: "train" (no cache) | "prefill" (build cache) | "decode" (use+update).
    ``is_global``: for attn_type=="mixed"/"sliding", False -> windowed.
    ``pages``: paged decode — ``pages`` carries the block tables
    ({"block": (B, nb)} plus {"local": (B, nl)} when ring/window leaves
    are paged) and ``"layer"``, this layer's index (a tuple of scalars)
    into the whole pools (*L, P, page_size, KV, hd) the cache leaves
    then are.
    In prefill mode a ``cache`` holding {"k", "v", "hpos"} is a shared
    prefix HISTORY: queries attend over history + fresh KV (suffix
    prefill for COW prefix sharing) and only the fresh KV is returned.
    """
    b, s, d = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def get_lora(tag):
        return (lora or {}).get(tag)

    q = L.linear(p["q"], x, get_lora("q"), gates).reshape(b, s, h, hd)
    k = L.linear(p["k"], x, get_lora("k"), gates).reshape(b, s, kvh, hd)
    v = L.linear(p["v"], x, get_lora("v"), gates).reshape(b, s, kvh, hd)

    if cfg.use_qk_norm:
        q = _qk_norm(p["q_norm"], q, cfg.norm_eps)
        k = _qk_norm(p["k_norm"], k, cfg.norm_eps)

    # continuous batching: decode may carry PER-ROW positions (B,) — each
    # slot of the batch sits at its own sequence depth
    row_pos = None
    if mode == "decode" and getattr(positions, "ndim", 0) == 1 \
            and positions.shape[0] == b and (b > 1 or pages is not None):
        row_pos = positions

    if rope_enabled:
        theta = cfg.rope_theta_global if (
            is_global and cfg.rope_theta_global) else cfg.rope_theta
        rope_pos = row_pos[:, None] if row_pos is not None else positions
        q = L.rope(q, rope_pos, theta)
        k = L.rope(k, rope_pos, theta)

    window = 0
    if cfg.attn_type == "sliding" or (cfg.attn_type == "mixed" and not is_global):
        window = cfg.sliding_window

    if mode == "train":
        pos1d = positions if positions.ndim == 1 else positions[0]
        out = chunked_causal_attention(q, k, v, pos1d, pos1d, window)
        new_cache = None
    elif mode == "prefill":
        pos1d = positions if positions.ndim == 1 else positions[0]
        if cache is not None and "hpos" in cache:
            # suffix prefill against a shared-prefix history: the
            # history KV was computed once (B=1) by the prefix prefill;
            # causal masking makes those values independent of any
            # suffix, so attending suffix queries over [history; fresh]
            # with explicit absolute positions reproduces exactly what
            # a full-prompt prefill would have computed at these rows.
            hk = jnp.broadcast_to(cache["k"], (b,) + cache["k"].shape[1:])
            hv = jnp.broadcast_to(cache["v"], (b,) + cache["v"].shape[1:])
            kv_pos = jnp.concatenate([cache["hpos"], pos1d])
            out = chunked_causal_attention(
                q, jnp.concatenate([hk, k], axis=1),
                jnp.concatenate([hv, v], axis=1),
                pos1d, kv_pos, window, chunk=max(1024, s))
        else:
            out = chunked_causal_attention(q, k, v, pos1d, pos1d, window)
        new_cache = {"k": k, "v": v}
    elif mode == "decode" and pages is not None:
        # paged decode (see "Paged KV layout" above): scatter the new
        # token into this layer's pages of the pool, gather the row's
        # mapped pages back into the dense rowwise layout, and run the
        # IDENTICAL rowwise attention — bit-for-bit the dense path
        # (extra slots are masked to exact zero weight).
        rp = row_pos if row_pos is not None else jnp.reshape(positions, (b,))
        layer = pages["layer"]
        ps = cache["k"].shape[len(layer) + 1]
        local = pages.get("local")
        if window and local is not None:
            table, slot, n_slots = local, jnp.mod(rp, window), window
        else:
            table, slot = pages["block"], rp
            n_slots = pages["block"].shape[1] * ps
        ck = scatter_page_token(cache["k"], table, rp, slot, k[:, 0],
                                n_slots, layer)
        cv = scatter_page_token(cache["v"], table, rp, slot, v[:, 0],
                                n_slots, layer)

        def rows(pool):
            a = pool[layer]
            a = a.reshape((a.shape[0] * ps,) + a.shape[2:])
            return gather_pages(a, table, n_slots, ps)
        gk, gv = rows(ck), rows(cv)
        if window and local is not None:
            out = rowwise_ring_decode_attention(q, gk, gv, rp, window)
        else:
            out = rowwise_decode_attention(q, gk, gv, rp, window)
        new_cache = {"k": ck, "v": cv}
    elif mode == "decode" and row_pos is not None:
        if window and cache["k"].shape[1] == window:
            # ring cache + per-row positions: row b writes its new KV
            # into slot pos_b[b] % window (each row at its own ring
            # index); parked rows (pos >= FREED_POS, freed on EOS) get
            # an out-of-range slot so the write drops instead of
            # spraying garbage into their ring buffer
            slot = jnp.where(row_pos < FREED_POS,
                             jnp.mod(row_pos, window), window)
            ck = constrain(cache["k"].at[jnp.arange(b), slot].set(
                k[:, 0], mode="drop"), "cache_kv")
            cv = constrain(cache["v"].at[jnp.arange(b), slot].set(
                v[:, 0], mode="drop"), "cache_kv")
            out = rowwise_ring_decode_attention(q, ck, cv, row_pos, window)
        else:
            # each row scatters its new KV at its own position; parked
            # rows (pos = FREED_POS >> max_seq, drained slots) drop the
            # update harmlessly via mode="drop"
            ck = constrain(cache["k"].at[jnp.arange(b), row_pos].set(
                k[:, 0], mode="drop"), "cache_kv")
            cv = constrain(cache["v"].at[jnp.arange(b), row_pos].set(
                v[:, 0], mode="drop"), "cache_kv")
            out = rowwise_decode_attention(q, ck, cv, row_pos, window)
        new_cache = {"k": ck, "v": cv}
    elif mode == "decode":
        pos = positions if positions.ndim == 0 else positions.reshape(())
        ring = window and cache["k"].shape[1] == window
        if ring:
            # ring buffer: sliding-window layers keep only `window` slots
            # (beyond-paper §Perf: cuts local-layer cache footprint by
            # seq_len/window, e.g. 1024x for gemma3 @ 500k)
            slot = jnp.mod(pos, window)
            ck = constrain(jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k, slot, axis=1), "cache_kv")
            cv = constrain(jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v, slot, axis=1), "cache_kv")
            out = ring_decode_attention(q, ck, cv, pos, window)
        else:
            ck = constrain(jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k, pos, axis=1), "cache_kv")
            cv = constrain(jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v, pos, axis=1), "cache_kv")
            out = decode_attention(q, ck, cv, pos, window)
        new_cache = {"k": ck, "v": cv}
    else:
        raise ValueError(mode)

    y = L.linear(p["o"], out.reshape(b, s, h * hd), get_lora("o"), gates)
    return y, new_cache
