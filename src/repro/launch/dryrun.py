"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
combination on the production mesh and extract roofline terms.

MUST set the placeholder device count before ANY jax import — jax locks
the device count on first init.

Cost accounting (see EXPERIMENTS.md §Dry-run methodology):
XLA's ``cost_analysis`` counts ``while``-loop bodies ONCE, so a rolled
126-layer scan under-reports FLOPs/bytes/collectives by ~126x.  Fully
unrolling the real depth compiles in O(15 min) per combo on this 1-core
box — infeasible for 40+ combos.  We therefore:

  1. compile the REAL config with rolled scans (seconds) — this is the
     pass/fail lowering proof and the source of memory_analysis();
  2. compile two DEPTH PROBES (2 and 4 layers / 1 and 2 groups, fully
     unrolled — fast) and extrapolate linearly in depth: per-layer cost
     is exactly additive because every layer lowers to identical HLO;
  3. for mamba chunk scans (a second rolled loop over sequence chunks)
     a 2-point ``ssm_unroll`` probe isolates the per-chunk cost.

The extrapolated numbers are exact for the uniform stacks (verified by
test_dryrun_probes.py against small fully-unrolled compiles).
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
from typing import Any, Dict, Optional, Tuple  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (ASSIGNED_ARCHS, INPUT_SHAPES, get_config,  # noqa: E402
                           shape_applicable)
from repro.core import fusion as FUS       # noqa: E402
from repro.launch import analysis as AN    # noqa: E402
from repro.launch import sharding as SH    # noqa: E402
from repro.launch.mesh import auto_mesh, make_production_mesh  # noqa: E402
from repro.models.model import LM          # noqa: E402
from repro.training import optimizer as OPT  # noqa: E402
from repro.training import train_step as TS  # noqa: E402


# ---------------------------------------------------------------------------
# Abstract inputs (ShapeDtypeStructs — never allocated)
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def abstract_lora_bank(lm, num_experts: int, rank: int):
    """SDS tree of a LoRA bank (model-facing, no metadata)."""
    layout = lm.lora_layout()
    out = {}
    for stack, (dims, targets) in layout.items():
        out[stack] = {
            tgt: {"A": _sds(dims + (num_experts, rank, din), jnp.float32),
                  "B": _sds(dims + (num_experts, dout, rank), jnp.float32)}
            for tgt, (din, dout) in targets.items()
        }
    return out


def lora_bank_shardings(bank_abs, mesh):
    """A: shard d_in (last) over data; B: shard d_out (dim -2) over model."""
    sizes = dict(mesh.shape)

    def walk(node, name=None):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        spec = [None] * len(node.shape)
        if name == "A" and "data" in sizes \
                and node.shape[-1] % sizes["data"] == 0:
            spec[-1] = "data"
        if name == "B" and "model" in sizes \
                and node.shape[-2] % sizes["model"] == 0:
            spec[-2] = "model"
        return NamedSharding(mesh, P(*spec))
    return walk(bank_abs)


def input_specs(arch_or_cfg, shape_name: str) -> Dict[str, Any]:
    """Abstract model inputs for one (arch, shape): tokens/frames/patches,
    targets+mask (train).  Weak-type-correct, shardable, no allocation."""
    cfg = (get_config(arch_or_cfg) if isinstance(arch_or_cfg, str)
           else arch_or_cfg)
    sh = INPUT_SHAPES[shape_name]
    b, s = sh.global_batch, sh.seq_len
    d = {}
    if sh.kind in ("train", "prefill"):
        n_tok = s
        if cfg.family == "vlm":
            n_tok = s - cfg.num_patches
            d["patches"] = _sds((b, cfg.num_patches, cfg.d_model),
                                jnp.bfloat16)
        if cfg.family == "audio":
            d["frames"] = _sds((b, cfg.encoder_seq, cfg.d_model),
                               jnp.bfloat16)
        d["tokens"] = _sds((b, n_tok), jnp.int32)
        if sh.kind == "train":
            d["targets"] = _sds((b, n_tok), jnp.int32)
            d["mask"] = _sds((b, n_tok), jnp.float32)
    else:  # decode
        d["tokens"] = _sds((b, 1), jnp.int32)
    return d


def batch_shardings(batch_abs, mesh):
    return {k: SH.batch_sharding(mesh, v.shape[0], len(v.shape))
            for k, v in batch_abs.items()}


# ---------------------------------------------------------------------------
# One compile
# ---------------------------------------------------------------------------


def compile_combo(cfg, shape, mesh, *, optimizer: str = "adamw",
                  absorb: bool = False, unroll: bool = False,
                  ssm_unroll: int = 1, want_hlo: bool = False,
                  act_policy: str = "pinned",
                  param_rules: str = "fsdp",
                  ring_cache: bool = False,
                  kv_shard: str = "heads") -> Dict:
    """Lower + compile one (config, shape) on `mesh`.  Returns cost dict."""
    from repro.models import sharding_hooks as HOOKS
    lm = LM(cfg, remat=(shape.kind == "train"), unroll_layers=unroll,
            ssm_unroll=ssm_unroll, ring_cache=ring_cache)
    lm.kv_shard = kv_shard
    if act_policy in ("pinned", "seqpar"):
        HOOKS.set_policy(SH.make_activation_policy(
            cfg, mesh, shape.global_batch,
            shard_seq=(shape.global_batch == 1),
            seqpar=(act_policy == "seqpar"),
            seq_len=shape.seq_len if shape.kind != "decode" else 0,
            kv_seq_model=(kv_shard == "seq")))
    else:
        HOOKS.set_policy(None)
    params_abs = lm.abstract_params()
    params_sh = SH.param_shardings(None, lm.param_specs(), mesh,
                                   rules=SH.RULESETS[param_rules])
    batch_abs = input_specs(cfg, shape.name)
    batch_sh = batch_shardings(batch_abs, mesh)
    rep = NamedSharding(mesh, P())

    t0 = time.time()
    try:
        compiled = _lower_compile(lm, cfg, shape, mesh, optimizer, absorb,
                                  params_abs, params_sh, batch_abs, batch_sh,
                                  rep)
    finally:
        HOOKS.set_policy(None)
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):       # jax<=0.4: one dict per device
        cost = cost[0] if cost else {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = AN.parse_collective_bytes(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(sum(coll.values())),
        "coll_by_type": coll,
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "arg_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "compile_s": t_compile,
        "hlo": hlo if want_hlo else None,
    }


def _lower_compile(lm, cfg, shape, mesh, optimizer, absorb, params_abs,
                   params_sh, batch_abs, batch_sh, rep):
    with mesh:
        if shape.kind == "train":
            opt = (OPT.adafactor(OPT.constant_schedule(1e-4))
                   if optimizer == "adafactor" else
                   OPT.adamw(OPT.constant_schedule(1e-4),
                             state_dtype=jnp.bfloat16
                             if optimizer == "adamw_bf16" else jnp.float32))
            bank_abs = abstract_lora_bank(lm, 1, cfg.lora_rank_max)
            opt_abs = jax.eval_shape(opt.init, bank_abs)
            bank_sh = lora_bank_shardings(bank_abs, mesh)
            opt_sh = _mirror_opt_shardings(opt_abs, bank_sh, mesh)

            def step(params, bank, opt_state, batch, gates):
                loss, grads = jax.value_and_grad(
                    lambda bk: TS.lora_loss_fn(lm, params, bk, batch,
                                               gates))(bank)
                bank2, opt2 = opt.update(grads, opt_state, bank)
                return bank2, opt2, loss

            jitted = jax.jit(step, in_shardings=(
                params_sh, bank_sh, opt_sh, batch_sh, rep))
            lowered = jitted.lower(params_abs, bank_abs, opt_abs, batch_abs,
                                   _sds((1,), jnp.float32))
        elif shape.kind == "prefill":
            e = cfg.num_lora_experts
            bank_abs = abstract_lora_bank(lm, e, cfg.lora_rank_max)
            bank_sh = lora_bank_shardings(bank_abs, mesh)

            def step(params, bank, gates, batch):
                return lm.prefill(params, batch, shape.seq_len, lora=bank,
                                  gates=gates)

            jitted = jax.jit(step, in_shardings=(
                params_sh, bank_sh, rep, batch_sh))
            lowered = jitted.lower(
                params_abs, bank_abs,
                _sds((shape.global_batch, e), jnp.float32), batch_abs)
        else:
            e = cfg.num_lora_experts
            bank_abs = abstract_lora_bank(lm, e, cfg.lora_rank_max)
            bank_sh = lora_bank_shardings(bank_abs, mesh)
            cache_abs = jax.eval_shape(
                lambda: lm.init_cache(shape.global_batch, shape.seq_len))
            cache_sh = SH.cache_shardings(cfg, cache_abs, mesh,
                                          shard_seq=(shape.global_batch == 1),
                                          kv_seq_model=(lm.kv_shard == "seq"))

            def step(params, bank, gates, cache, tokens):
                return lm.decode_step(params, cache, tokens, lora=bank,
                                      gates=gates, absorb=absorb)

            # donate the cache: in-place dynamic-update-slice instead of
            # full-cache copies (matches real serving; also keeps probe
            # cost_analysis free of copy artifacts)
            jitted = jax.jit(step, in_shardings=(
                params_sh, bank_sh, rep, cache_sh, batch_sh["tokens"]),
                donate_argnums=(3,))
            lowered = jitted.lower(
                params_abs, bank_abs,
                _sds((shape.global_batch, e), jnp.float32), cache_abs,
                batch_abs["tokens"])
        return lowered.compile()


def _mirror_opt_shardings(opt_abs, bank_sh, mesh):
    rep = NamedSharding(mesh, P())
    out = {}
    for k, v in opt_abs.items():
        if k in ("m", "v"):
            out[k] = bank_sh
        else:
            out[k] = jax.tree.map(lambda _: rep, v)
    return out


# ---------------------------------------------------------------------------
# Depth-extrapolated exact costs
# ---------------------------------------------------------------------------

_KEYS = ("flops", "bytes", "coll")


def _vec(c: Dict) -> Dict:
    out = {k: c[k] for k in _KEYS}
    out["coll_by_type"] = dict(c["coll_by_type"])
    return out


def _lin(a, sa, b=None, sb=0.0):
    """sa*a + sb*b over cost vectors (incl. per-type collectives)."""
    out = {k: sa * a[k] + (sb * b[k] if b else 0.0) for k in _KEYS}
    keys = set(a["coll_by_type"]) | set(b["coll_by_type"] if b else {})
    out["coll_by_type"] = {
        k: sa * a["coll_by_type"].get(k, 0.0)
        + (sb * b["coll_by_type"].get(k, 0.0) if b else 0.0)
        for k in keys}
    return out


def _add(a, b):
    return _lin(a, 1.0, b, 1.0)


def _relu(a):
    """Clamp a cost vector at zero (probe diffs can go slightly negative
    when XLA fuses the 2x-unrolled chunk body more aggressively)."""
    out = {k: max(0.0, a[k]) for k in _KEYS}
    out["coll_by_type"] = {k: max(0.0, v)
                           for k, v in a["coll_by_type"].items()}
    return out


def _variant(cfg, **kw):
    return dataclasses.replace(cfg, **kw)


def extrapolate_costs(cfg, shape, mesh, *, optimizer="adamw",
                      absorb=False, verbose=False, act_policy="pinned",
                      param_rules="fsdp", ring_cache=False,
                      kv_shard="heads") -> Tuple[Dict, Dict]:
    """Exact per-step costs via depth probes.  Returns (costs, meta)."""
    kind = shape.kind
    meta: Dict[str, Any] = {"probes": []}

    def probe(c, ssm_u=1):
        r = compile_combo(c, shape, mesh, optimizer=optimizer,
                          absorb=absorb, unroll=True, ssm_unroll=ssm_u,
                          act_policy=act_policy, param_rules=param_rules,
                          ring_cache=ring_cache, kv_shard=kv_shard)
        meta["probes"].append({"layers": c.num_layers, "ssm_u": ssm_u,
                               "compile_s": r["compile_s"],
                               "flops": r["flops"]})
        return _vec(r)

    needs_ssm = bool(cfg.ssm_version) and kind in ("train", "prefill")
    chunk = 128 if cfg.ssm_version == 1 else 256
    nc = shape.seq_len // chunk if needs_ssm else 0

    if cfg.family == "audio":
        a = probe(_variant(cfg, num_layers=2, encoder_layers=2))
        b = probe(_variant(cfg, num_layers=4, encoder_layers=4))
        pair = _lin(b, 0.5, a, -0.5)
        total = _add(a, _lin(pair, float(cfg.num_layers - 2)))
    elif cfg.family == "moe" and cfg.first_k_dense:
        a = probe(_variant(cfg, first_k_dense=0, num_layers=2))
        b = probe(_variant(cfg, first_k_dense=0, num_layers=4))
        moe_l = _lin(b, 0.5, a, -0.5)
        c_ = probe(_variant(cfg, first_k_dense=2, num_layers=2))
        d_ = probe(_variant(cfg, first_k_dense=4, num_layers=4))
        dense_l = _lin(d_, 0.5, c_, -0.5)
        base = _lin(a, 1.0, moe_l, -2.0)
        total = _add(base, _add(_lin(dense_l, float(cfg.first_k_dense)),
                                _lin(moe_l,
                                     float(cfg.num_layers
                                           - cfg.first_k_dense))))
    elif cfg.family == "hybrid" and cfg.attn_every:
        g = cfg.attn_every
        n_groups = cfg.num_layers // g
        tail = cfg.num_layers - n_groups * g
        a = probe(_variant(cfg, num_layers=g + tail))
        b = probe(_variant(cfg, num_layers=2 * g + tail))
        group = _lin(b, 1.0, a, -1.0)
        total = _add(a, _lin(group, float(n_groups - 1)))
        if needs_ssm:
            a2 = probe(_variant(cfg, num_layers=g + tail), ssm_u=2)
            loops_in_a = (g - 1) + tail          # mamba layers in probe A
            c_body = _relu(_lin(a2, 1.0 / loops_in_a, a, -1.0 / loops_in_a))
            mamba_layers = cfg.num_layers - n_groups  # non-attn layers
            total = _add(total, _lin(c_body,
                                     float((nc - 1) * mamba_layers)))
    elif cfg.attn_type == "mixed" and cfg.global_every:
        g = cfg.global_every
        n_groups = cfg.num_layers // g
        tail = cfg.num_layers - n_groups * g
        a = probe(_variant(cfg, num_layers=g + tail))
        b = probe(_variant(cfg, num_layers=2 * g + tail))
        group = _lin(b, 1.0, a, -1.0)
        total = _add(a, _lin(group, float(n_groups - 1)))
    else:
        # plain uniform stack (dense / vlm / ssm / moe-without-kd)
        a = probe(_variant(cfg, num_layers=2))
        b = probe(_variant(cfg, num_layers=4))
        layer = _lin(b, 0.5, a, -0.5)
        total = _add(a, _lin(layer, float(cfg.num_layers - 2)))
        if needs_ssm:
            a2 = probe(_variant(cfg, num_layers=2), ssm_u=2)
            c_body = _relu(_lin(a2, 0.5, a, -0.5))  # 2 chunk loops in A
            total = _add(total, _lin(c_body,
                                     float((nc - 1) * cfg.num_layers)))
    meta["nc"] = nc
    return _relu(total), meta


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            optimizer: str = "adamw", absorb: bool = False,
            save_hlo: Optional[str] = None, verbose: bool = True,
            skip_probes: bool = False, act_policy: str = "pinned",
            param_rules: str = "fsdp", mesh_shape: Optional[str] = None,
            ring_cache: bool = False, kv_shard: str = "heads",
            tag: str = "") -> Optional[Dict]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        if verbose:
            print(f"SKIP {arch} × {shape_name}: {reason}")
        return {"arch": arch, "shape": shape_name, "skipped": reason,
                "tag": tag, "multi_pod": multi_pod}

    if mesh_shape:
        dims = tuple(int(x) for x in mesh_shape.split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
        mesh = auto_mesh(dims, axes)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    mesh_name = "x".join(str(x) for x in mesh.devices.shape)

    # 1) the lowering proof: REAL config, rolled scans, real memory numbers
    real = compile_combo(cfg, shape, mesh, optimizer=optimizer,
                         absorb=absorb, unroll=False, want_hlo=bool(save_hlo),
                         act_policy=act_policy, param_rules=param_rules,
                         ring_cache=ring_cache, kv_shard=kv_shard)
    if save_hlo and real["hlo"]:
        with open(save_hlo, "w") as f:
            f.write(real["hlo"])

    # 2) exact costs via depth probes
    if skip_probes:
        costs, pmeta = _vec(real), {"probes": [], "nc": 0}
    else:
        costs, pmeta = extrapolate_costs(cfg, shape, mesh,
                                         optimizer=optimizer, absorb=absorb,
                                         act_policy=act_policy,
                                         param_rules=param_rules,
                                         ring_cache=ring_cache,
                                         kv_shard=kv_shard)

    tokens = shape.global_batch * (
        1 if shape.kind == "decode" else shape.seq_len)
    model_fl = AN.model_flops_estimate(cfg, tokens, shape.kind,
                                       context=shape.seq_len)

    rl = AN.Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=costs["flops"] * chips, hlo_bytes=costs["bytes"] * chips,
        collective_bytes=costs["coll"] * chips,
        coll_by_type=costs["coll_by_type"], model_flops=model_fl,
        per_device_bytes=real["temp_bytes"],
        argument_bytes=real["arg_bytes"],
    )
    row = rl.row()
    row.update({
        "compile_s": real["compile_s"], "optimizer": optimizer,
        "absorb": absorb, "multi_pod": multi_pod, "tag": tag,
        "act_policy": act_policy, "param_rules": param_rules,
        "ring_cache": ring_cache, "kv_shard": kv_shard,
        "total_params": AN.total_params(cfg),
        "active_params": AN.active_params(cfg),
        "probe_meta": pmeta,
        "rolled_flops_per_dev": real["flops"],
        "output_bytes": real["output_bytes"],
    })
    if verbose:
        print(f"OK {arch} × {shape_name} @ {mesh_name} "
              f"(compile {real['compile_s']:.1f}s, "
              f"{len(pmeta['probes'])} probes)")
        print(f"   per-dev: flops={costs['flops']:.3e} "
              f"bytes={costs['bytes']:.3e} coll={costs['coll']:.3e}")
        print(f"   roofline: compute={rl.t_compute*1e3:.3f}ms "
              f"memory={rl.t_memory*1e3:.3f}ms "
              f"collective={rl.t_collective*1e3:.3f}ms "
              f"-> {rl.dominant}-bound; useful={rl.useful_flops_ratio:.3f}")
        print(f"   memory_analysis/device: args={real['arg_bytes']} "
              f"temp={real['temp_bytes']}")
    return row


# ---------------------------------------------------------------------------
# Floe fusion co-serving dry-run (the paper-representative pair)
# ---------------------------------------------------------------------------


def run_fusion(shape_name: str = "decode_32k", *, multi_pod: bool = False,
               verbose: bool = True, tag: str = "",
               slm_arch: str = "floe-slm-2b", llm_arch: str = "floe-llm-7b",
               probes: bool = True, param_rules: str = "fsdp",
               kv_shard: str = "heads") -> Dict:
    """LLM + SLM parallel decode + logit fusion (Eq. 12-15) as one pjit
    step on the production mesh."""
    shape = INPUT_SHAPES[shape_name]
    b, s = shape.global_batch, shape.seq_len
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    mesh_name = "x".join(str(x) for x in mesh.devices.shape)

    def compile_pair(slm_cfg, llm_cfg, unroll):
        slm = LM(slm_cfg, remat=False, unroll_layers=unroll)
        llm = LM(llm_cfg, remat=False, unroll_layers=unroll)
        e = slm_cfg.num_lora_experts
        bank_abs = abstract_lora_bank(slm, e, slm_cfg.lora_rank_max)
        mlp_abs = jax.eval_shape(
            lambda: FUS.init_alignment(jax.random.key(0),
                                       slm_cfg.vocab_size))

        def step(sp, lp, mlp, bank, gates, s_cache, l_cache, tokens):
            sl, s_cache = slm.decode_step(sp, s_cache, tokens, lora=bank,
                                          gates=gates)
            ll, l_cache = llm.decode_step(lp, l_cache, tokens)
            p, w = FUS.fused_distribution(mlp, sl[:, 0], ll[:, 0])
            return p, w, s_cache, l_cache

        sp_abs, lp_abs = slm.abstract_params(), llm.abstract_params()
        sc_abs = jax.eval_shape(lambda: slm.init_cache(b, s))
        lc_abs = jax.eval_shape(lambda: llm.init_cache(b, s))
        rep = NamedSharding(mesh, P())
        t0 = time.time()
        from repro.models import sharding_hooks as HOOKS
        HOOKS.set_policy(SH.make_activation_policy(
            slm_cfg, mesh, b, shard_seq=(b == 1),
            kv_seq_model=(kv_shard == "seq")))
        rules = SH.RULESETS[param_rules]
        with mesh:
            jitted = jax.jit(step, in_shardings=(
                SH.param_shardings(None, slm.param_specs(), mesh, rules),
                SH.param_shardings(None, llm.param_specs(), mesh, rules),
                jax.tree.map(lambda _: rep, mlp_abs),
                lora_bank_shardings(bank_abs, mesh),
                rep,
                SH.cache_shardings(slm_cfg, sc_abs, mesh,
                                   shard_seq=(b == 1),
                                   kv_seq_model=(kv_shard == "seq")),
                SH.cache_shardings(llm_cfg, lc_abs, mesh,
                                   shard_seq=(b == 1),
                                   kv_seq_model=(kv_shard == "seq")),
                SH.batch_sharding(mesh, b, 2)))
            lowered = jitted.lower(sp_abs, lp_abs, mlp_abs, bank_abs,
                                   _sds((b, e), jnp.float32), sc_abs, lc_abs,
                                   _sds((b, 1), jnp.int32))
            compiled = lowered.compile()
        HOOKS.set_policy(None)
        cost = compiled.cost_analysis() or {}
        coll = AN.parse_collective_bytes(compiled.as_text())
        mem = compiled.memory_analysis()
        return {"flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0)),
                "coll": float(sum(coll.values())),
                "coll_by_type": coll,
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "arg_bytes": getattr(mem, "argument_size_in_bytes", None),
                "compile_s": time.time() - t0}

    s_cfg, l_cfg = get_config(slm_arch), get_config(llm_arch)
    real = compile_pair(s_cfg, l_cfg, False)
    if probes:
        a = _vec(compile_pair(_variant(s_cfg, num_layers=2),
                              _variant(l_cfg, num_layers=2), True))
        bb = _vec(compile_pair(_variant(s_cfg, num_layers=4),
                               _variant(l_cfg, num_layers=4), True))
        pair_layer = _lin(bb, 0.5, a, -0.5)
        # slm and llm depths differ: scale by each stack's extra depth is
        # approximated by the mean extra depth (both dense decoders)
        extra = (s_cfg.num_layers - 2) + (l_cfg.num_layers - 2)
        costs = _add(a, _lin(pair_layer, extra / 2.0))
    else:
        costs = _vec(real)

    model_fl = (AN.model_flops_estimate(s_cfg, b, "decode", s)
                + AN.model_flops_estimate(l_cfg, b, "decode", s))
    rl = AN.Roofline("floe-fusion", shape_name, mesh_name, chips,
                     costs["flops"] * chips, costs["bytes"] * chips,
                     costs["coll"] * chips, costs["coll_by_type"], model_fl,
                     per_device_bytes=real["temp_bytes"],
                     argument_bytes=real["arg_bytes"])
    row = rl.row()
    row.update({"compile_s": real["compile_s"], "multi_pod": multi_pod,
                "tag": tag, "slm": slm_arch, "llm": llm_arch,
                "param_rules": param_rules, "kv_shard": kv_shard})
    if verbose:
        print(f"OK floe-fusion × {shape_name} @ {mesh_name} "
              f"(compile {real['compile_s']:.1f}s)")
        print(f"   roofline: compute={rl.t_compute*1e3:.3f}ms "
              f"memory={rl.t_memory*1e3:.3f}ms "
              f"collective={rl.t_collective*1e3:.3f}ms -> {rl.dominant}")
    return row


# ---------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fusion", action="store_true")
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adamw_bf16", "adafactor"])
    ap.add_argument("--absorb", action="store_true",
                    help="MLA absorbed decode (optimized path)")
    ap.add_argument("--skip-probes", action="store_true",
                    help="lowering proof only (fast; rolled-loop costs)")
    ap.add_argument("--act-policy", default="pinned",
                    choices=["pinned", "seqpar", "none"])
    ap.add_argument("--param-rules", default="fsdp",
                    choices=["fsdp", "inference"])
    ap.add_argument("--mesh", default=None,
                    help="override mesh shape, e.g. 4x64")
    ap.add_argument("--ring-cache", action="store_true",
                    help="window-sized ring KV cache for sliding layers")
    ap.add_argument("--kv-shard", default="heads",
                    choices=["heads", "seq"],
                    help="decode cache sharding over `model`: kv-heads/"
                         "head_dim vs sequence (flash-decode style)")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    def emit(r):
        if r is None:
            return
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(r) + "\n")
        import sys
        sys.stdout.flush()

    if args.fusion:
        emit(run_fusion(args.shape or "decode_32k",
                        multi_pod=args.multi_pod, tag=args.tag,
                        param_rules=args.param_rules,
                        kv_shard=args.kv_shard))
    elif args.all:
        for arch in ASSIGNED_ARCHS:
            for shape in INPUT_SHAPES:
                try:
                    emit(run_one(arch, shape, multi_pod=args.multi_pod,
                                 optimizer=args.optimizer, tag=args.tag,
                                 skip_probes=args.skip_probes))
                except Exception as e:        # noqa: BLE001
                    print(f"FAIL {arch} × {shape}: {type(e).__name__}: {e}")
                    emit({"arch": arch, "shape": shape,
                          "error": str(e), "tag": args.tag})
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        emit(run_one(args.arch, args.shape,
                     multi_pod=args.multi_pod,
                     optimizer=args.optimizer, absorb=args.absorb,
                     save_hlo=args.save_hlo, tag=args.tag,
                     skip_probes=args.skip_probes,
                     act_policy=args.act_policy,
                     param_rules=args.param_rules,
                     mesh_shape=args.mesh,
                     ring_cache=args.ring_cache,
                     kv_shard=args.kv_shard))


if __name__ == "__main__":
    main()
