"""The main serving path's Pallas kernels compile for a TPU v5e at the
pair's published widths, and its paged decode step updates the page
pool in place.

Nothing here runs on a chip: the TPU compiler compiles for a described
``v5e:2x2`` topology, which refuses what interpret mode accepts — blocks
off the (8, 128) tiling, kernels over the VMEM budget.  Each compile
takes a second or two.  The topology is described only inside the
fixture below (never at import), and the compilation cache is off
around these compiles: a TPU program written to it cannot be read back
without a chip.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.kernels.logit_fusion import ops as FOPS
from repro.kernels.moe_lora.kernel import moe_lora_delta_slots
from repro.models.model import LM

VOCAB = get_config("floe-slm-2b").vocab_size       # 256 000


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means skip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# 1 and 4 are padded to one 8-row block; 32 is a k=4 speculative
# verify over a batch of 8
@pytest.mark.parametrize("b", [1, 4, 8, 32])
def test_logit_fusion_compiles_at_full_vocab(one_chip, b, monkeypatch):
    # the serving wrapper picks interpret mode from the default backend,
    # which is the CPU here; the described chip gets the real kernel
    monkeypatch.setattr(FOPS, "_on_cpu", lambda: False)
    logits = _arg((b, VOCAB), jnp.float32, one_chip)
    compiled = FOPS.fused_probs_masked.lower(
        logits, logits, _arg((b,), jnp.float32, one_chip),
        _arg((b,), jnp.bool_, one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("target", ["q", "mlp_in", "mlp_out"])
def test_moe_lora_slots_compiles_at_slm_widths(one_chip, target):
    cfg = get_config("floe-slm-2b")
    d_in, d_out = {"q": (cfg.d_model, cfg.num_heads * cfg.head_dim),
                   "mlp_in": (cfg.d_model, 2 * cfg.d_ff),
                   "mlp_out": (cfg.d_ff, cfg.d_model)}[target]
    t, e, r = 8, cfg.num_lora_experts, cfg.lora_rank_max
    compiled = jax.jit(moe_lora_delta_slots).lower(
        _arg((t, d_in), jnp.bfloat16, one_chip),
        _arg((e, r, d_in), jnp.bfloat16, one_chip),
        _arg((e, d_out, r), jnp.bfloat16, one_chip),
        _arg((t,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


# ------------------------------------------------------ paged decode step

# Phi-3-mini's widths: 32 heads of 96, every head its own KV head
PHI3_MINI = dict(d_model=3072, num_heads=32, num_kv_heads=32, head_dim=96,
                 d_ff=8192, vocab_size=32064)
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(")


def _paged_decode_scan(sharding, layers, pages, rows=2, page_size=128,
                       steps=8):
    """``steps`` paged decode steps of a Phi-3-mini-width stack in one
    ``lax.scan`` over a donated cache: the shape of a lane's macro
    step, compiled."""
    cfg = ModelConfig(name="phi3-mini-paged", family="dense", source="test",
                      num_layers=layers, attn_type="full", mlp_type="swiglu",
                      norm_eps=1e-5, dtype="bfloat16", **PHI3_MINI)
    lm = LM(cfg, remat=False)
    params = jax.tree.map(lambda a: _arg(a.shape, a.dtype, sharding),
                          lm.abstract_params())
    pool = (layers, pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    cache = {"k": _arg(pool, jnp.bfloat16, sharding),
             "v": _arg(pool, jnp.bfloat16, sharding),
             "pos": _arg((rows,), jnp.int32, sharding),
             "block": _arg((rows, pages), jnp.int32, sharding)}

    def step(p, c, tok):
        def body(c, _):
            logits, c = lm.decode_step(p, c, tok)
            return c, logits[:, 0, :8]
        return jax.lax.scan(body, c, None, length=steps)

    return jax.jit(step, donate_argnums=1).lower(
        params, cache, _arg((rows, 1), jnp.int32, sharding)).compile()


def _while_instructions(hlo: str):
    """Instruction lines of every computation a while loop runs: its
    bodies and conditions, and what they call, transitively."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line.startswith("  "):
            if name is not None:
                comps[name].append(line)
        elif line and not line.startswith("}"):
            name = re.match(r"(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            comps[name] = []
    callee = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
    todo = [b for lines in comps.values() for ln in lines
            for b in re.findall(r"\bbody=%?([\w.\-]+)", ln)]
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += callee.findall("\n".join(comps.get(c, ())))
    assert seen, "no while loop in the compiled step"
    return [ln for c in seen for ln in comps.get(c, ())]


def test_paged_decode_updates_the_pool_in_place(one_chip):
    """Paged decode carries the page pools through the layer scan and
    writes each new token into them in place.  Scanning the pools as
    layer ``xs`` instead rebuilds them as ``ys`` (a whole-pool
    dynamic-update-slice per layer) and copies them back into the token
    scan's carry on every token.

    Two layers: at three or four the compiler stages a pool in VMEM and
    the temporaries no longer count its copies as they do at the full
    32.  The pool's cost is read against a one-page pool of the same
    stack: the pool may add at most one pool's tiled size of
    temporaries (the layout change at the step's entry and exit)."""
    layers, pages, ps, kv, hd = 2, 10, 128, 32, 96
    one = _paged_decode_scan(one_chip, layers, 1)
    full = _paged_decode_scan(one_chip, layers, pages)
    n_pool = layers * pages * ps * kv * hd
    pool_ops = []
    for ln in _while_instructions(full.as_text()):
        m = _INSTR.match(ln)
        if m and m.group(3) in ("copy", "copy-done", "dynamic-update-slice"):
            dims = [int(d) for d in m.group(2).split(",") if d]
            if math.prod(dims) == n_pool:
                pool_ops.append((m.group(1), m.group(2)))
    assert not pool_ops, f"pool-sized copies inside the loops: {pool_ops}"
    # the loop tiles (KV, hd) as (8, 128): hd pads to 128; K and V, bf16
    pool_tiled = 2 * layers * pages * ps * kv * 128 * 2
    t_one = one.memory_analysis().temp_size_in_bytes
    t_full = full.memory_analysis().temp_size_in_bytes
    assert t_full < t_one + pool_tiled, (t_one, t_full, pool_tiled)
