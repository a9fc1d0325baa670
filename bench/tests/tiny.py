"""A CPU-sized copy of a benchmark cell for the tests: the same
configuration file with every width cut, a short mix, in a scratch root."""
from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "vocab_size": 512}


def tiny_config(name: str = "phi3-pair-1chip") -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    for side, layers, kv in (("slm", 2, 4), ("llm", 1, 2)):
        cfg[side].update(SMALL, num_hidden_layers=layers,
                         num_key_value_heads=kv)
    cfg["engine"].update(cloud_rows=2, edge_rows=2, max_seq=256,
                         max_ctx=256, page_size=32, slm_pool_pages=16,
                         llm_pool_pages=16, prefill_chunk=32,
                         chunk_width=32, macro_k=4)
    cfg["mesh"] = None
    return cfg


TINY_MIX = {"kind": "open", "why": "tiny", "rate_per_s": 4.0,
            "prompt_tokens": {"dist": "lognormal", "median": 40,
                              "sigma": 0.8, "min": 16, "max": 120},
            "output_tokens": {"dist": "lognormal", "median": 8,
                              "sigma": 0.5, "min": 4, "max": 24},
            "private_share": 0.25, "warmup_s": 1.0, "schedule_seed": 1}


def scratch_root(tmp: Path, mix: dict = None, limit: float = 0.25,
                 cell: str = "tiny.chat") -> Path:
    """A checkout-like directory: BENCHMARK.json naming one tiny cell,
    its configuration, and a data directory with its mix and limits."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test", "file":
                         "tiny.json", "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": cell, "config": "tiny",
                           "traffic": "tiny", "chips": 1, "why": "test"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "tiny.json").write_text(json.dumps(tiny_config()))
    (tmp / "traffic").mkdir(exist_ok=True)
    (tmp / "traffic" / "tiny.json").write_text(json.dumps(mix or TINY_MIX))
    (tmp / "limits").mkdir(exist_ok=True)
    (tmp / "limits" / f"{cell}.json").write_text(json.dumps({"limits": {
        "served_gap_max": limit, "route_mismatch": 0, "private_fused": 0,
        "cloud_replies_lost": 0, "never_served": 0}}))
    return tmp


class Args:
    def __init__(self, workload="tiny.chat", seed=5, seconds=3.0, trace=0):
        self.workload, self.seed = workload, seed
        self.seconds, self.trace = seconds, trace
