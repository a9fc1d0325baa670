"""The main serving path's Pallas kernels compile for a TPU v5e at the
pair's published widths.

Nothing here runs on a chip: the TPU compiler compiles for a described
``v5e:2x2`` topology, which refuses what interpret mode accepts — blocks
off the (8, 128) tiling, kernels over the VMEM budget.  Each compile
takes a second or two.  The topology is described only inside the
fixture below (never at import), and the compilation cache is off
around these compiles: a TPU program written to it cannot be read back
without a chip.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.logit_fusion import ops as FOPS
from repro.kernels.moe_lora.kernel import moe_lora_delta_slots

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
