"""Merged multi-LoRA delta Pallas kernel — Floe Eq. 8 inference hot path.

Computes  Δy[t] = Σ_j ω[t,j] · (x[t] A_jᵀ) B_jᵀ   for a token block.

Grid: (T_blocks, E) — experts on the innermost (sequential) axis; the
(bt × n_out) accumulator lives in VMEM scratch and is emitted after the
last expert.  Per step the kernel does two small MXU matmuls
(bt×k · k×r, then bt×r · r×n), so arithmetic intensity stays high even
at rank 16-64.  VMEM budget per step ≈ bt·k + r·k + n·r + bt·n floats —
callers pick bt so this stays under the ~16 MiB VMEM bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_lora_kernel(x_ref, a_ref, b_ref, g_ref, o_ref, acc_ref, *, ne: int):
    ei = pl.program_id(1)

    @pl.when(ei == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)            # (bt, k)
    a = a_ref[0].astype(jnp.float32)              # (r, k)
    bmat = b_ref[0].astype(jnp.float32)           # (n, r)
    g = g_ref[...].astype(jnp.float32)            # (bt, 1)

    u = jnp.dot(x, a.T, preferred_element_type=jnp.float32)     # (bt, r)
    u = u * g                                                    # ω_j gate
    acc_ref[...] += jnp.dot(u, bmat.T, preferred_element_type=jnp.float32)

    @pl.when(ei == ne - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_lora_delta(x, a, b, gates, *, block_t: int = 128,
                   interpret: bool = False):
    """x: (T, k); a: (E, r, k); b: (E, n, r); gates: (T, E) -> (T, n)."""
    t, k = x.shape
    e, r, _ = a.shape
    n = b.shape[1]
    bt = min(block_t, t)
    assert t % bt == 0, (t, bt)

    kernel = functools.partial(_moe_lora_kernel, ne=e)
    return pl.pallas_call(
        kernel,
        grid=(t // bt, e),
        in_specs=[
            pl.BlockSpec((bt, k), lambda ti, ei: (ti, 0)),
            pl.BlockSpec((1, r, k), lambda ti, ei: (ei, 0, 0)),
            pl.BlockSpec((1, n, r), lambda ti, ei: (ei, 0, 0)),
            pl.BlockSpec((bt, 1), lambda ti, ei: (ti, ei)),
        ],
        out_specs=pl.BlockSpec((bt, n), lambda ti, ei: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((t, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, n), jnp.float32)],
        interpret=interpret,
    )(x, a, b, gates)


def _moe_lora_slots_kernel(slots_ref, x_ref, a_ref, b_ref, o_ref):
    s = slots_ref[pl.program_id(0)]
    valid = (s >= 0).astype(jnp.float32)           # negative slot -> 0.0
    x = x_ref[0].astype(jnp.float32)               # (1, k)
    a = a_ref[0].astype(jnp.float32)               # (r, k)
    bmat = b_ref[0].astype(jnp.float32)            # (n, r)
    u = jnp.dot(x, a.T, preferred_element_type=jnp.float32)
    o_ref[0] = (valid * jnp.dot(
        u, bmat.T, preferred_element_type=jnp.float32)).astype(o_ref.dtype)


def moe_lora_delta_slots(x, a, b, slots, *, interpret: bool = False):
    """x: (T, k); a: (E, r, k); b: (E, n, r); slots: (T,) int32 -> (T, n).

    Per-row slot-gather variant of ``moe_lora_delta`` for a ONE-HOT gate
    matrix: row t applies only slot[t]'s adapter, so the dense Σ over E
    is skipped entirely — the scalar-prefetched slot ids drive the
    BlockSpec index maps (the adapter analogue of the paged-attention
    block-table gather), DMA-ing exactly one (r,k)+(n,r) expert per row.
    Negative slots (adapter-free rows) are clamped onto slot 0 for the
    fetch and masked to an exact 0.0 in-kernel, matching the all-zero
    gate row of the dense path bit for bit.

    Rows travel as (T, 1, k) / (T, 1, n) so each one-row block spans
    the array's last two dims whole: a (1, k) block of a (T, k) array
    breaks the TPU's 8-row tiling rule for any T > 1."""
    t, k = x.shape
    e, r, _ = a.shape
    n = b.shape[1]

    def expert_map(ti, slots_ref):
        return (jnp.clip(slots_ref[ti], 0, e - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, 1, k), lambda ti, slots_ref: (ti, 0, 0)),
            pl.BlockSpec((1, r, k), expert_map),
            pl.BlockSpec((1, n, r), expert_map),
        ],
        out_specs=pl.BlockSpec((1, 1, n), lambda ti, slots_ref: (ti, 0, 0)),
    )
    out = pl.pallas_call(
        _moe_lora_slots_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, n), x.dtype),
        interpret=interpret,
    )(slots.astype(jnp.int32), x.reshape(t, 1, k), a, b)
    return out.reshape(t, n)
