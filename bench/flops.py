"""Operations and bytes of the served work, computed from shapes.

Kept with the benchmark so that every PR counts the same work the same
way.  Model dims use the Hugging Face keys of a configuration file.
Matmul FLOPs count 2 per multiply-add; elementwise work counts 1 per
operation (a transcendental counts 1).
"""
from __future__ import annotations

import re


def _dims(m: dict):
    d = m["hidden_size"]
    hd = d // m["num_attention_heads"]
    return (d, m["intermediate_size"], m["num_attention_heads"],
            m["num_key_value_heads"], hd, m["vocab_size"],
            m["num_hidden_layers"])


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, f, h, kv, hd, _, _ = _dims(m)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def token_flops(m: dict, ctx: int) -> int:
    """One token through the whole model at context length ``ctx``
    (the token attends to ``ctx`` positions, itself included): the
    projections and MLP of every layer, attention scores and values,
    and the unembedding."""
    d, f, h, kv, hd, v, n = _dims(m)
    per_layer = 2 * layer_matmul_params(m) + 4 * h * hd * ctx
    return n * per_layer + 2 * d * v


# the Floe Eq. 15 fusion kernel, per element of its float32 result: two
# subtractions, two exponentials, two divisions, two products, one sum
FUSION_FLOPS_PER_ELEMENT = 9


DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
               "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
               "f8e4m3fn": 1, "f8e5m2": 1}
SHAPE = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\](\{[^}]*\})?")


def _shape_bytes(m) -> tuple:
    """(bytes, memory space) of one HLO shape ``dtype[dims]{layout}``;
    space 1 is the TPU's VMEM (``S(1)`` in the layout), 0 is HBM."""
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    space = re.search(r"S\((\d+)\)", m.group(3) or "")
    return n * DTYPE_BYTES[m.group(1)], int(space.group(1)) if space else 0


def op_traffic(op_text: str) -> dict:
    """Bytes an HLO op reads (its operands) and writes (its result),
    per memory space, from the op's text as a device trace names it:
    ``%name = <result shape> op(<operand shape> %arg, ...), ...``."""
    rhs = op_text.split(" = ", 1)[1]
    call = re.search(r"\s([a-z][\w\-.]*)\(", rhs)
    if call is None:
        raise ValueError(f"not an HLO op: {op_text[:80]!r}")
    depth, end = 0, None
    for i in range(call.end() - 1, len(rhs)):
        depth += {"(": 1, ")": -1}.get(rhs[i], 0)
        if depth == 0:
            end = i
            break
    out = {}
    for kind, text in (("write", rhs[:call.start()]),
                       ("read", rhs[call.end():end])):
        for m in SHAPE.finditer(text):
            if m.group(1) not in DTYPE_BYTES:
                continue
            b, space = _shape_bytes(m)
            out[(kind, space)] = out.get((kind, space), 0) + b
    return out


def op_least_seconds(flops: float, traffic: dict, peak: dict):
    """(least seconds, bound) for an op: the largest of its compute time
    at the bf16 peak, its HBM bytes (read + written) at HBM bandwidth,
    and its VMEM reads and writes at the VMEM read and write bandwidths.
    Each is a time the chip cannot beat, so their maximum is too."""
    hbm = traffic.get(("read", 0), 0) + traffic.get(("write", 0), 0)
    times = {"compute": flops / peak["bf16_flops_per_s"],
             "hbm": hbm / peak["hbm_bytes_per_s"],
             "vmem read": traffic.get(("read", 1), 0)
             / peak["vmem_read_bytes_per_s"],
             "vmem write": traffic.get(("write", 1), 0)
             / peak["vmem_write_bytes_per_s"]}
    bound = max(times, key=times.get)
    return times[bound], bound
