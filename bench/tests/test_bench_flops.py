"""FLOP and byte counts on shapes worked by hand, and the peak table."""
import json

import pytest

import tiny
import flops as F
import run

M = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
     "num_key_value_heads": 1, "vocab_size": 10, "num_hidden_layers": 3}


def test_layer_params_by_hand():
    # q 8x8, k and v 8x4 each, o 8x8, mlp in 8x32 and out 16x8
    assert F.layer_matmul_params(M) == 64 + 32 + 32 + 64 + 256 + 128


def test_token_flops_by_hand():
    # per layer 2 * 576 matmul + 4 * 2 heads * 4 dims * ctx 5 attention
    per_layer = 2 * 576 + 4 * 8 * 5
    assert F.token_flops(M, 5) == 3 * per_layer + 2 * 8 * 10


PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0,
        "vmem_read_bytes_per_s": 40.0, "vmem_write_bytes_per_s": 20.0}


def test_op_traffic_by_hand():
    op = ("%k.3 = f32[8,16]{1,0:T(8,128)S(1)} custom-call(f32[8,16]"
          "{1,0:T(8,128)S(1)} %a, bf16[8,16]{1,0:T(8,128)} %b, s32[]"
          "{:T(128)} %c), custom_call_target=\"tpu_custom_call\"")
    assert F.op_traffic(op) == {("write", 1): 512, ("read", 1): 512,
                                ("read", 0): 256 + 4}
    tup = "%t = (s32[2]{0}, f32[4]{0:S(1)}) fusion(u8[3]{0} %x), kind=kLoop"
    assert F.op_traffic(tup) == {("write", 0): 8, ("write", 1): 16,
                                 ("read", 0): 3}


def test_least_time_is_the_largest_bound():
    traffic = {("read", 0): 30, ("write", 0): 10, ("read", 1): 40,
               ("write", 1): 100}
    assert F.op_least_seconds(50, traffic, PEAK) == (5.0, "vmem write")
    assert F.op_least_seconds(900, traffic, PEAK) == (9.0, "compute")
    assert F.op_least_seconds(0, {("read", 0): 70}, PEAK) == (7.0, "hbm")


def test_peaks_table_has_its_source():
    peaks = json.loads((tiny.BENCH / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["vmem_read_bytes_per_s"] > v5e["hbm_bytes_per_s"]


def test_unknown_device_kind_is_an_error():
    class Dev:
        platform, device_kind = "cpu", "TPU v99"

    with pytest.raises(run.BenchError, match="not in peaks.json"):
        run.check_peaks(Dev())


def test_no_accelerator_is_an_error():
    with pytest.raises(run.BenchError, match="no accelerator"):
        run.find_devices(1, platform="tpu")
