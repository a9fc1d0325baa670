"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (deliverable c).

All kernels run in interpret mode on CPU (the kernel body itself
executes); on TPU the same pallas_call lowers to Mosaic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.logit_fusion.kernel import fuse_logits
from repro.kernels.logit_fusion.ref import fuse_logits_ref
from repro.kernels.moe_lora.kernel import (moe_lora_delta,
                                           moe_lora_delta_slots)
from repro.kernels.moe_lora.ref import (moe_lora_delta_ref,
                                        moe_lora_delta_slots_ref)
from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_decode_ref
from repro.kernels.ssm_scan.kernel import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ----------------------------------------------------------------- flash


@pytest.mark.parametrize("b,h,kvh,s,d", [
    (1, 2, 1, 32, 16),
    (2, 4, 2, 64, 32),
    (1, 8, 8, 128, 64),
    (2, 4, 1, 64, 128),     # extreme GQA (gemma3-style)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 16),
                                           (False, 0)])
def test_flash_attention_sweep(b, h, kvh, s, d, dtype, causal, window):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kvh, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kvh, s, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_k=32, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_block_shape_independence():
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32))
    k = jax.random.normal(ks[1], (1, 2, 128, 32))
    v = jax.random.normal(ks[2], (1, 2, 128, 32))
    outs = [flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
            for bq, bk in [(32, 32), (64, 32), (128, 128), (32, 64)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------------------------ paged attention


def _paged_case(key, b, h, kvh, hd, n_pool, ps, nb, window, dtype):
    """Random pool + a block table shaped like the allocator would build
    it: plain rows map exactly the pages their position needs (sentinel
    past that); ring rows map a full page ring."""
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, hd), dtype)
    pk = jax.random.normal(ks[1], (n_pool, ps, kvh, hd), dtype)
    pv = jax.random.normal(ks[2], (n_pool, ps, kvh, hd), dtype)
    rng = np.random.default_rng(int(jax.random.randint(ks[0], (), 0, 1 << 30)))
    free = list(rng.permutation(n_pool))
    if window:
        pos = jnp.asarray(rng.integers(0, 3 * window, (b,)), jnp.int32)
        table = np.asarray([[free.pop() for _ in range(nb)]
                            for _ in range(b)], np.int32)
    else:
        pos = jnp.asarray(rng.integers(0, nb * ps, (b,)), jnp.int32)
        table = np.full((b, nb), 1 << 20, np.int32)      # NO_PAGE sentinel
        for i in range(b):
            for t in range(int(pos[i]) // ps + 1):
                table[i, t] = free.pop()
    return q, pk, pv, jnp.asarray(table), pos


@pytest.mark.parametrize("b,h,kvh,hd,n_pool,ps,nb", [
    (3, 4, 2, 16, 12, 4, 3),
    (2, 8, 4, 32, 16, 8, 2),
    (4, 4, 1, 64, 20, 16, 3),     # extreme GQA, serving page size
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(b, h, kvh, hd, n_pool, ps, nb, dtype):
    q, pk, pv, table, pos = _paged_case(
        jax.random.key(11), b, h, kvh, hd, n_pool, ps, nb, 0, dtype)
    out = paged_decode_attention(q, pk, pv, table, pos, interpret=True)
    ref = paged_decode_ref(q, pk, pv, table, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window,ps,nb", [
    (12, 4, 3),     # window == nb*ps: exact page ring
    (10, 4, 3),     # window < nb*ps: tail slots of the ring masked out
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_ring(window, ps, nb, dtype):
    q, pk, pv, table, pos = _paged_case(
        jax.random.key(12), 3, 4, 2, 16, 12, ps, nb, window, dtype)
    out = paged_decode_attention(q, pk, pv, table, pos, window=window,
                                 interpret=True)
    ref = paged_decode_ref(q, pk, pv, table, pos, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_attention_matches_dense_gather_path():
    """Kernel agrees with the model's jnp paged-decode math: gather the
    pages dense (models.attention.gather_pages) and run the rowwise
    decode the serving engine uses."""
    from repro.models import attention as ATT
    b, h, kvh, hd, n_pool, ps, nb = 3, 4, 2, 16, 12, 4, 3
    q, pk, pv, table, pos = _paged_case(
        jax.random.key(13), b, h, kvh, hd, n_pool, ps, nb, 0, jnp.float32)
    out = paged_decode_attention(q, pk, pv, table, pos, interpret=True)
    flat = lambda a: a.reshape((n_pool * ps,) + a.shape[2:])
    gk = ATT.gather_pages(flat(pk), table, nb * ps, ps)
    gv = ATT.gather_pages(flat(pv), table, nb * ps, ps)
    ref = ATT.rowwise_decode_attention(q[:, None], gk, gv, pos)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# -------------------------------------------------------------- moe_lora


@pytest.mark.parametrize("t,k,e,r,n", [
    (32, 16, 2, 4, 32),
    (64, 64, 4, 8, 48),
    (128, 32, 8, 16, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_lora_sweep(t, k, e, r, n, dtype):
    ks = jax.random.split(jax.random.key(2), 4)
    x = jax.random.normal(ks[0], (t, k), dtype)
    a = (jax.random.normal(ks[1], (e, r, k)) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[2], (e, n, r)) * 0.1).astype(dtype)
    g = jax.nn.softmax(jax.random.normal(ks[3], (t, e))).astype(dtype)
    out = moe_lora_delta(x, a, b, g, block_t=32, interpret=True)
    ref = moe_lora_delta_ref(x, a, b, g)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype] * 4, rtol=TOL[dtype] * 4)


def test_moe_lora_gate_zero_kills_expert():
    ks = jax.random.split(jax.random.key(3), 4)
    t, k, e, r, n = 32, 16, 3, 4, 16
    x = jax.random.normal(ks[0], (t, k))
    a = jax.random.normal(ks[1], (e, r, k))
    b = jax.random.normal(ks[2], (e, n, r))
    g = jnp.zeros((t, e)).at[:, 0].set(1.0)
    full = moe_lora_delta(x, a, b, g, block_t=32, interpret=True)
    only0 = moe_lora_delta_ref(x, a[:1], b[:1], jnp.ones((t, 1)))
    np.testing.assert_allclose(np.asarray(full), np.asarray(only0),
                               atol=1e-4)


@pytest.mark.parametrize("t,k,e,r,n", [
    (8, 16, 2, 4, 32),
    (16, 64, 4, 8, 48),
    (32, 32, 8, 16, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_lora_slots_sweep(t, k, e, r, n, dtype):
    """Slot-gather kernel vs the one-hot dense oracle, adapter-free
    rows (slot -1) interleaved — must be exactly the one-hot gates
    result, including the exact-0.0 rows."""
    ks = jax.random.split(jax.random.key(7), 3)
    x = jax.random.normal(ks[0], (t, k), dtype)
    a = (jax.random.normal(ks[1], (e, r, k)) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[2], (e, n, r)) * 0.1).astype(dtype)
    slots = jnp.asarray([(i % (e + 1)) - 1 for i in range(t)], jnp.int32)
    out = moe_lora_delta_slots(x, a, b, slots, interpret=True)
    ref = moe_lora_delta_slots_ref(x, a, b, slots)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype] * 4, rtol=TOL[dtype] * 4)
    none_rows = np.asarray(slots) < 0
    assert np.all(np.asarray(out, np.float32)[none_rows] == 0.0)


def test_moe_lora_slots_matches_dense_onehot():
    """The slot kernel is bit-comparable to the DENSE kernel fed the
    equivalent one-hot gate matrix (the engine's two execution paths)."""
    ks = jax.random.split(jax.random.key(9), 3)
    t, k, e, r, n = 32, 16, 4, 4, 16
    x = jax.random.normal(ks[0], (t, k))
    a = jax.random.normal(ks[1], (e, r, k))
    b = jax.random.normal(ks[2], (e, n, r))
    slots = jnp.asarray(np.arange(t) % e, jnp.int32)
    g = jax.nn.one_hot(slots, e, dtype=jnp.float32)
    dense = moe_lora_delta(x, a, b, g, block_t=32, interpret=True)
    gathered = moe_lora_delta_slots(x, a, b, slots, interpret=True)
    np.testing.assert_allclose(np.asarray(gathered), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)


# -------------------------------------------------------------- ssm_scan


@pytest.mark.parametrize("b,s,di,n,chunk,bd", [
    (1, 32, 32, 8, 8, 16),
    (2, 64, 64, 16, 16, 32),
    (1, 128, 256, 16, 64, 128),
])
def test_ssm_scan_sweep(b, s, di, n, chunk, bd):
    ks = jax.random.split(jax.random.key(4), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, s, di))) * 0.1
    x = jax.random.normal(ks[1], (b, s, di))
    bm = jax.random.normal(ks[2], (b, s, n)) * 0.5
    cm = jax.random.normal(ks[3], (b, s, n)) * 0.5
    a = -jnp.exp(jax.random.normal(ks[4], (di, n)) * 0.3)
    y, h = ssm_scan(dt, x, bm, cm, a, chunk=chunk, block_d=bd,
                    interpret=True)
    yr, hr = ssm_scan_ref(dt, x, bm, cm, a)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4)


def test_ssm_scan_matches_model_inner():
    """Kernel agrees with the model's chunked associative-scan path."""
    from repro.configs import get_config
    from repro.models import ssm as MSSM
    cfg = get_config("falcon-mamba-7b").reduced()
    ks = jax.random.split(jax.random.key(5), 5)
    b, s, di, n = 2, 32, cfg.d_inner, cfg.ssm_state
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, s, di))) * 0.1
    x = jax.random.normal(ks[1], (b, s, di))
    bm = jax.random.normal(ks[2], (b, s, n)) * 0.5
    cm = jax.random.normal(ks[3], (b, s, n)) * 0.5
    a_log = jax.random.normal(ks[4], (di, n)) * 0.3
    p = {"A_log": a_log}
    y1, h1 = MSSM._mamba1_inner(cfg, p, x, dt, bm, cm,
                                jnp.zeros((b, di, n)), chunk=16)
    y2, h2 = ssm_scan(dt, x, bm, cm, -jnp.exp(a_log), chunk=16,
                      block_d=di, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4)


# ---------------------------------------------------------- logit fusion


@pytest.mark.parametrize("b,v", [(4, 128), (8, 1000), (2, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_logit_fusion_sweep(b, v, dtype):
    ks = jax.random.split(jax.random.key(6), 3)
    sl = jax.random.normal(ks[0], (b, v), dtype)
    ll = jax.random.normal(ks[1], (b, v), dtype)
    w = jax.nn.sigmoid(jax.random.normal(ks[2], (b,)))
    out = fuse_logits(sl, ll, w, block_b=2, interpret=True)
    ref = fuse_logits_ref(sl, ll, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-3 if dtype == jnp.bfloat16 else 1e-6)
    np.testing.assert_allclose(np.asarray(out.sum(-1)), 1.0, atol=1e-3)


@pytest.mark.parametrize("v,block_v", [(4096, 1024), (1000, 384)])
def test_logit_fusion_vocab_tiles(v, block_v):
    """Rows wider than one vocab tile: lane-aligned tiles that divide V,
    and a V no multiple of 128 divides (padded with -inf logits)."""
    ks = jax.random.split(jax.random.key(9), 3)
    sl = jax.random.normal(ks[0], (8, v)) * 3.0
    ll = jax.random.normal(ks[1], (8, v)) * 3.0
    w = jax.nn.sigmoid(jax.random.normal(ks[2], (8,)))
    arrived = jnp.arange(8) % 3 != 0
    out = fuse_logits(sl, ll, w, arrived=arrived, block_v=block_v,
                      interpret=True)
    ref = fuse_logits_ref(sl, ll, w, arrived)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("b", [1, 3, 5, 8])
def test_logit_fusion_ragged_batch(b):
    """Ragged serving batches: ops wrapper pads B up to a block_b
    multiple, masks the padded rows, and slices them away."""
    from repro.kernels.logit_fusion.ops import fused_probs_masked
    ks = jax.random.split(jax.random.key(7), 3)
    v = 257
    sl = jax.random.normal(ks[0], (b, v))
    ll = jax.random.normal(ks[1], (b, v))
    w = jax.nn.sigmoid(jax.random.normal(ks[2], (b,)))
    arrived = jnp.asarray([i % 2 == 0 for i in range(b)])
    out = fused_probs_masked(sl, ll, w, arrived, block_b=4)
    assert out.shape == (b, v)
    ref = fuse_logits_ref(sl, ll, w, arrived)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    # arrived=False rows are pure SLM (w forced to 1)
    p_slm = jax.nn.softmax(sl, -1)
    for i in range(b):
        if not bool(arrived[i]):
            np.testing.assert_allclose(np.asarray(out[i]),
                                       np.asarray(p_slm[i]), atol=1e-6)


def test_logit_fusion_arrived_in_kernel():
    """Per-row arrived mask applied inside the Pallas kernel body."""
    ks = jax.random.split(jax.random.key(8), 3)
    sl = jax.random.normal(ks[0], (4, 64))
    ll = jax.random.normal(ks[1], (4, 64))
    w = jax.nn.sigmoid(jax.random.normal(ks[2], (4,)))
    arrived = jnp.asarray([True, False, True, False])
    out = fuse_logits(sl, ll, w, arrived=arrived, block_b=2, interpret=True)
    ref = fuse_logits_ref(sl, ll, w, arrived)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
