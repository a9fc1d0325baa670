"""The system under test, built from a configuration file.

Everything here adapts the benchmark's data (a configuration's JSON, the
seeded weights of ``weights.py``) to the program's own entry points:
``repro.configs.base.ModelConfig``, ``repro.models.model.LM``,
``ServingDeployment`` and ``BatchedHybridEngine``.  Nothing decides a
result here; the comparison lives in ``check.py``.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

import jax
import jax.numpy as jnp

import weights as W


def load_config(path) -> dict:
    with open(path) as f:
        cfg = json.load(f)
    for side in ("slm", "llm"):
        m = cfg[side]
        if m["hidden_size"] % m["num_attention_heads"]:
            raise ValueError(f"{path}: {side} head size is not whole")
    if cfg["slm"]["vocab_size"] != cfg["llm"]["vocab_size"]:
        raise ValueError(f"{path}: a Floe pair shares one vocabulary")
    return cfg


def model_config(m: dict, name: str):
    """A program ModelConfig for HF-style dims ``m`` (dense, full
    attention: every served context is inside the sliding window)."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=name, family="dense", source=name,
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m["hidden_size"] // m["num_attention_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        attn_type="full", rope_theta=float(m["rope_theta"]),
        mlp_type="swiglu", norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]), dtype="bfloat16")


def program_tree(native: Dict[str, Any]) -> Dict[str, Any]:
    """The benchmark's weight layout -> the program's parameter tree."""
    ly = native["layers"]
    return {
        "embed": {"tok": {"w": native["tok"]},
                  "unembed": {"w": native["unembed"]}},
        "ln_f": {"scale": native["ln_f"]},
        "layers": {
            "ln1": {"scale": ly["ln1"]},
            "attn": {"q": {"w": ly["wq"]}, "k": {"w": ly["wk"]},
                     "v": {"w": ly["wv"]}, "o": {"w": ly["wo"]}},
            "ln2": {"scale": ly["ln2"]},
            "mlp": {"in": {"w": ly["w_in"]}, "out": {"w": ly["w_out"]}},
        },
    }


def _same_layout(tree, abstract, what: str):
    got = jax.tree.map(lambda x: (x.shape, jnp.dtype(x.dtype)), tree)
    want = jax.tree.map(lambda x: (x.shape, jnp.dtype(x.dtype)), abstract)
    if jax.tree.structure(got) != jax.tree.structure(want) or \
            jax.tree.leaves(got) != jax.tree.leaves(want):
        raise RuntimeError(f"{what}: the program's parameter layout is not "
                           "the one system.program_tree builds")


class System:
    """Models, seeded weights on the devices, deployment and engine."""

    def __init__(self, cfg: dict, seed: int, mesh=None):
        from repro.models.model import LM
        from repro.serving.deployment import (ServingDeployment,
                                              alignment_shardings,
                                              model_param_shardings)
        from repro.serving.engine import BatchedHybridEngine
        from repro.serving.latency import LatencyModel
        self.cfg = cfg
        e = cfg["engine"]
        self.slm = LM(model_config(cfg["slm"], "slm"), remat=False)
        self.llm = LM(model_config(cfg["llm"], "llm"), remat=False)
        vocab = cfg["slm"]["vocab_size"]
        init = cfg["weights"]
        key = W.base_key(seed)
        sh = None
        if mesh is not None:
            sh = (model_param_shardings(self.slm, mesh),
                  model_param_shardings(self.llm, mesh),
                  alignment_shardings(vocab, mesh))

        def draw(key):
            # the key is an argument, not a constant: one compiled draw
            # serves every seed (and comes from the compile cache)
            s = program_tree(W.draw_stacked(key, "slm", cfg["slm"], init))
            l_ = program_tree(W.draw_stacked(key, "llm", cfg["llm"], init))
            return s, l_, W.draw_align(key, vocab, init)

        abstract = jax.eval_shape(draw, key)
        _same_layout(abstract[0], self.slm.abstract_params(), "slm")
        _same_layout(abstract[1], self.llm.abstract_params(), "llm")
        sp, lp, mlp = jax.jit(draw, out_shardings=sh)(key)
        jax.block_until_ready((sp, lp, mlp))
        self.dep = ServingDeployment(
            self.slm, sp, self.llm, lp, mlp, latency=LatencyModel(),
            timeout_ms=float(e["timeout_ms"]), max_seq=e["max_seq"],
            page_size=e["page_size"], max_ctx=e["max_ctx"], mesh=mesh)
        del sp, lp, mlp
        self.engine = BatchedHybridEngine(
            deployment=self.dep, batch_size=e["cloud_rows"],
            edge_batch_size=e["edge_rows"],
            prefill_chunk=e["prefill_chunk"], macro_k=e["macro_k"],
            pool_pages=e["slm_pool_pages"],
            llm_pool_pages=e["llm_pool_pages"],
            chunk_width=e["chunk_width"], spec_k=e["spec_k"])

    def lanes(self):
        eng = self.engine
        return (eng.cloud_lane, eng.edge_lane)

    def close(self):
        """Free every device buffer the system holds (params, lane caches
        and logits), so the reference that follows has the chip."""
        held = [self.dep.slm_params, self.dep.llm_params, self.dep.mlp]
        for lane in self.lanes():
            held += [lane.s_cache, lane.l_cache, lane.sl, lane.ll, lane.lt,
                     lane.gates]
        for x in jax.tree.leaves(held):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()
        self.engine = None
        self.dep = None
        self.slm = self.llm = None
        jax.clear_caches()


def config_path(root: Path, bench: dict, name: str) -> Path:
    for c in bench["configs"]:
        if c["name"] == name:
            return root / c["file"]
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")
