#!/usr/bin/env python3
"""Serve the Floe ``2b`` pair at published widths on a TPU, and check
every served token against a cache-free forward pass.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the whole pair, sharded

One chip holds floe-slm-2b whole (18 layers, ~2.5 B params) and
floe-llm-7b at its published widths (d_model 3072, 16x256 heads, d_ff
24 576, vocab 256 000) with as many whole layers as leave 3 GiB of HBM
free of params (the programs' temporaries take 1.1-1.4 GB of it, so
about 2 GB stays free at peak).  Its 28 layers (~17 GB in bf16) do not
fit one 16 GB chip.  With ``--four-chips`` both models keep their
published depth and are sharded over a 4-wide "model" mesh axis by the
inference rules; only that phase runs.  Weights are random, drawn from
fixed seeds.

Both phases serve the same 8 requests (a few prompt lengths, two of them
private, so they stay on the SLM-only edge lane) through the normal
entry points — ``ServingDeployment`` -> ``ContinuousBatchScheduler`` ->
``BatchedHybridEngine`` with paged KV and macro_k=8 — once at spec_k=0
and once at spec_k=4, each pass once to compile and once timed.  Every
response must be OK with 16 greedy tokens; each private row's tokens
must be the argmax of a cache-free SLM forward over prompt + emitted
tokens, and each cloud row's (every reply arrives: the timeout is
unbounded) the argmax of the cache-free fused distribution
(``core.fusion.fused_distribution``), on the same params and chip.  A
token may differ from that argmax only where the two are within
TIE_TOL in log-probability (a bf16 near-tie); the count is printed.

Everything but the last line is a report.  The last line of standard
output is {"ok": true, "device": {...}}, printed only when every check
passed.  Without a TPU, or without the ``repro`` package next to this
file, the script exits non-zero and prints no such line.  Compiled
programs persist in JAX_COMPILATION_CACHE_DIR when it is set, else in
``.jax_cache`` beside this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

PAIR = "2b"
BATCH = 4
MAX_NEW = 16
MACRO_K = 8
SPEC_K = 4
MAX_SEQ = 64
PAGE_SIZE = 16
HEADROOM_BYTES = 3 << 30           # HBM the params leave free on one chip
# a served token may differ from the reference argmax only where the
# two are this close in log-probability: 16 bf16 epsilons (2^-7) at
# unit scale, the size of the rounding between a paged-KV decode and a
# cache-free forward of the same bf16 model
TIE_TOL = 16 * 2.0 ** -7
TIMEOUT_MS = 1e9                   # every cloud reply arrives
# (prompt, private): a few lengths; the detector keeps two on the edge
PROMPTS = (
    ("math: compute 12 plus 7 =", False),
    ("my ssn is 123-45-6789", True),
    ("translate to french: water ->", False),
    ("my doctor said rest", True),
    ("sort: 40 12 77 31 ->", False),
    ("explain rainbows", False),
    ("hi", False),
    ("name three primes", False),
)
REF_ROWS = 4                       # reference forward, rows per call


class SmokeFailure(Exception):
    pass


def say(*parts):
    print(*parts, flush=True)


def tree_bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def model_bytes(cfg) -> int:
    from repro.models.model import LM
    return tree_bytes(LM(cfg).abstract_params())


def llm_layers_that_fit(slm_cfg, llm_cfg, bytes_limit: int) -> int:
    """The most whole LLM layers that leave HEADROOM_BYTES of the chip
    free next to the whole SLM and the alignment MLP."""
    import jax.numpy as jnp
    from repro.core import fusion as FUS
    from repro.models import layers as L
    fixed = (model_bytes(slm_cfg) + HEADROOM_BYTES + tree_bytes(
        L.abstract_params(FUS.alignment_spec(slm_cfg.vocab_size),
                          jnp.float32)))
    n = llm_cfg.num_layers
    while n > 0 and fixed + model_bytes(
            dataclasses.replace(llm_cfg, num_layers=n)) > bytes_limit:
        n -= 1
    if n == 0:
        raise SmokeFailure("not one LLM layer fits next to the SLM")
    return n


class CompileClock:
    """Seconds spent in XLA compilation (cache loads included) and the
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.programs += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.seconds, self.programs, self.cache_hits


def build(slm_cfg, llm_cfg, mesh=None):
    """Params drawn in place (sharded when ``mesh`` is given) and the
    deployment that serves them."""
    import jax
    from repro.core import fusion as FUS
    from repro.models.model import LM
    from repro.serving.deployment import (ServingDeployment,
                                          alignment_shardings,
                                          model_param_shardings)
    from repro.serving.latency import LatencyModel
    slm, llm = LM(slm_cfg, remat=False), LM(llm_cfg, remat=False)
    v = slm_cfg.vocab_size
    sh = (dict(slm=model_param_shardings(slm, mesh),
               llm=model_param_shardings(llm, mesh),
               mlp=alignment_shardings(v, mesh))
          if mesh is not None else dict(slm=None, llm=None, mlp=None))
    sp = slm.init(jax.random.key(0), sh["slm"])
    lp = llm.init(jax.random.key(1), sh["llm"])
    mlp = FUS.init_alignment(jax.random.key(2), v, shardings=sh["mlp"])
    jax.block_until_ready((sp, lp, mlp))
    return ServingDeployment(
        slm, sp, llm, lp, mlp, latency=LatencyModel(),
        timeout_ms=TIMEOUT_MS, max_seq=MAX_SEQ, page_size=PAGE_SIZE,
        mesh=mesh)


def serve(dep, spec_k: int):
    """One pass of the 8 requests; (responses, wall seconds)."""
    from repro.serving.scheduler import ContinuousBatchScheduler
    sched = ContinuousBatchScheduler.from_deployment(
        dep, batch_size=BATCH, macro_k=MACRO_K, spec_k=spec_k)
    for prompt, _ in PROMPTS:
        sched.submit(prompt, max_new_tokens=MAX_NEW)
    t0 = time.perf_counter()
    res = sched.run()
    return res, time.perf_counter() - t0


def check_responses(res):
    from repro.data import tokenizer as TOK
    from repro.serving.scheduler import ResponseStatus
    if len(res) != len(PROMPTS):
        raise SmokeFailure(f"{len(res)} responses for {len(PROMPTS)}")
    for r, (_, private) in zip(res, PROMPTS):
        st = r.stats
        ids = st.token_ids
        if r.status is not ResponseStatus.OK:
            raise SmokeFailure(f"request {r.rid}: status {r.status}")
        if st.private != private:
            raise SmokeFailure(f"request {r.rid}: private={st.private}")
        full = len(ids) == MAX_NEW
        if st.tokens != len(ids) or not (
                full or (ids and ids[-1] == TOK.EOS)):
            raise SmokeFailure(f"request {r.rid}: {st.tokens} tokens "
                               f"{ids}")
        if not private and st.cloud_tokens != st.tokens:
            raise SmokeFailure(f"request {r.rid}: {st.cloud_tokens} of "
                               f"{st.tokens} tokens fused the cloud")


def reference_gaps(dep, res):
    """Per served token: reference top-1 log-probability minus the
    served token's, under the cache-free SLM forward (private rows) or
    the cache-free fused distribution (cloud rows).  0 = the argmax."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import fusion as FUS
    from repro.data import tokenizer as TOK

    slm, llm = dep.slm, dep.llm

    def gaps(sp, lp, mlp, toks, pos, emitted):
        def at(logits):                  # (R, L, V) -> (R, n, V)
            return jnp.take_along_axis(logits, pos[..., None], axis=1)
        sl = at(slm.train_logits(sp, {"tokens": toks})[0])
        ll = at(llm.train_logits(lp, {"tokens": toks})[0])
        r, n, v = sl.shape
        p, _ = FUS.fused_distribution(mlp, sl.reshape(r * n, v),
                                      ll.reshape(r * n, v))
        out = []
        for logp in (jax.nn.log_softmax(sl.astype(jnp.float32), -1),
                     jnp.log(p).reshape(r, n, v)):
            got = jnp.take_along_axis(logp, emitted[..., None], -1)[..., 0]
            out.append(jnp.max(logp, -1) - got)
        return tuple(out)

    rows = []
    for r, (prompt, private) in zip(res, PROMPTS):
        ids = TOK.encode(prompt + " ")
        rows.append((ids, r.stats.token_ids, private))
    width = max(len(ids) + len(out) - 1 for ids, out, _ in rows)
    width = -(-width // PAGE_SIZE) * PAGE_SIZE
    nr = -(-len(rows) // REF_ROWS) * REF_ROWS
    toks = np.zeros((nr, width), np.int32)
    pos = np.zeros((nr, MAX_NEW), np.int32)
    emitted = np.zeros((nr, MAX_NEW), np.int32)
    for i, (ids, out, _) in enumerate(rows):
        seq = ids + out[:-1]
        toks[i, :len(seq)] = seq
        pos[i, :len(out)] = np.arange(len(out)) + len(ids) - 1
        emitted[i, :len(out)] = out
    fn = jax.jit(gaps)
    parts = [fn(dep.slm_params, dep.llm_params, dep.mlp,
                toks[i:i + REF_ROWS], pos[i:i + REF_ROWS],
                emitted[i:i + REF_ROWS])
             for i in range(0, nr, REF_ROWS)]
    g_slm = np.concatenate([np.asarray(p[0]) for p in parts])
    g_fused = np.concatenate([np.asarray(p[1]) for p in parts])
    return [(g_slm[i] if private else g_fused[i])[:len(out)]
            for i, (_, out, private) in enumerate(rows)]


def check_against_reference(dep, res, label: str):
    import numpy as np
    gaps = reference_gaps(dep, res)
    flat = np.concatenate(gaps)
    ties = int(((flat > 0) & (flat <= TIE_TOL)).sum())
    bad = int((flat > TIE_TOL).sum())
    say(f"{label}: reference check over {flat.size} tokens: "
        f"{flat.size - ties - bad} argmax, {ties} within the "
        f"{TIE_TOL} near-tie tolerance, {bad} beyond it "
        f"(largest gap {float(flat.max()):.4f})")
    if bad:
        where = [(r.rid, j, float(g[j])) for r, g in zip(res, gaps)
                 for j in range(len(g)) if g[j] > TIE_TOL]
        raise SmokeFailure(f"{label}: tokens off the reference argmax "
                           f"(rid, position, gap): {where[:10]}")


def serve_and_check(dep, spec_k: int, device_kind: str, clock):
    """One spec_k setting: a compiling pass, a timed pass that must
    serve the same tokens, and the reference check.  Returns the
    served token ids per request."""
    label = f"spec_k={spec_k}"
    c0 = clock.snapshot()
    first, wall0 = serve(dep, spec_k)
    c1 = clock.snapshot()
    check_responses(first)
    say(f"{label}: first pass {wall0:.3f} s wall, of it "
        f"{c1[0] - c0[0]:.3f} s compiling {c1[1] - c0[1]} programs "
        f"({c1[2] - c0[2]} from the persistent cache) [set-up]")
    res, wall = serve(dep, spec_k)
    c2 = clock.snapshot()
    check_responses(res)
    served = [r.stats.token_ids for r in res]
    if served != [r.stats.token_ids for r in first]:
        raise SmokeFailure(f"{label}: the two passes served different "
                           "tokens")
    say(f"{label}: timed pass served {sum(map(len, served))} tokens for "
        f"{len(res)} requests in {wall:.3f} s wall on {device_kind} "
        f"({c2[1] - c1[1]} compiles inside)")
    check_against_reference(dep, res, label)
    return served


def run_phase(dep, devices, clock) -> int:
    """Both serving passes and their checks.  Returns tokens served."""
    kind = devices[0].device_kind
    probe = _record_macro(dep)
    plain = serve_and_check(dep, 0, kind, clock)
    fused = probe()
    say(f"compiled macro step (cloud lane) contains the fusion kernel "
        f"(tpu_custom_call): {fused}")
    if not fused:
        raise SmokeFailure("the macro step has no Pallas kernel")
    spec = serve_and_check(dep, SPEC_K, kind, clock)
    same = sum(a == b for a, b in zip(plain, spec))
    say(f"spec_k={SPEC_K} served the same tokens as spec_k=0 on {same} "
        f"of {len(PROMPTS)} requests")
    for d in devices:
        st = d.memory_stats() or {}
        say(f"{d}: peak_bytes_in_use {st.get('peak_bytes_in_use')} of "
            f"bytes_limit {st.get('bytes_limit')}")
    return sum(map(len, plain)) + sum(map(len, spec))


def _record_macro(dep):
    """Wrap the cloud lane's macro step so its first call's argument
    shapes are kept; the returned probe compiles it again from those
    shapes (a cache hit) and says whether the fusion kernel is in it."""
    import jax
    orig, seen = dep.macro_cloud, {}

    def spec(x):
        # uncommitted host-made inputs (row ids, steps) follow the
        # params' devices, so they keep no sharding of their own
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=x.sharding if x.committed else None)
        return x

    def recording(*args):
        seen.setdefault("args", jax.tree.map(spec, args))
        return orig(*args)

    dep.macro_cloud = recording

    def probe():
        dep.macro_cloud = orig
        text = orig.lower(*seen["args"]).compile().as_text()
        return "tpu_custom_call" in text
    return probe


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve the whole pair at published depth, "
                         "sharded over four chips (only this phase)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing: {e}",
              file=sys.stderr)
        return 2
    import jax
    cache_dir = use_compile_cache()
    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU, JAX found {device.platform}",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    clock = CompileClock()
    from repro.configs.floe_pair import pair_configs
    slm_cfg, llm_cfg = pair_configs(PAIR, reduced=False)
    say(f"devices: {len(devices)} x {device.device_kind}; compile cache "
        f"{cache_dir}")
    t0 = time.perf_counter()
    mesh = None
    if args.four_chips:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(4, model_parallel=4)
        say(f"phase: four chips, mesh {dict(mesh.shape)}, inference rules")
        kept = llm_cfg.num_layers
    else:
        limit = (device.memory_stats() or {}).get("bytes_limit")
        if not limit:
            raise SmokeFailure("the device reports no bytes_limit")
        kept = llm_layers_that_fit(slm_cfg, llm_cfg, limit)
        say(f"phase: one chip, bytes_limit {limit}")
    cut = dataclasses.replace(llm_cfg, num_layers=kept)
    say(f"{slm_cfg.name}: whole, {slm_cfg.num_layers} layers, "
        f"{model_bytes(slm_cfg)} param bytes")
    say(f"{llm_cfg.name}: published widths (d_model {llm_cfg.d_model}, "
        f"{llm_cfg.num_heads}x{llm_cfg.head_dim} heads, d_ff "
        f"{llm_cfg.d_ff}, vocab {llm_cfg.vocab_size}); {kept} of "
        f"{llm_cfg.num_layers} layers kept"
        + ("" if kept == llm_cfg.num_layers else
           f" (cut: depth only, to leave {HEADROOM_BYTES} bytes free)")
        + f", {model_bytes(cut)} param bytes")
    dep = build(slm_cfg, cut, mesh)
    pd = dep.per_device_param_bytes()
    say(f"params: per-device {pd['total_bytes']} bytes "
        f"(all parts unsharded: {pd['replicated_bytes']}); drawn and "
        f"placed in {time.perf_counter() - t0:.3f} s [set-up]")
    tokens = run_phase(dep, devices[:need], clock)
    secs, programs, hits = clock.snapshot()
    say(f"compile total: {secs:.3f} s over {programs} programs, "
        f"{hits} persistent-cache hits [set-up]")
    say(f"served {tokens} checked tokens; all checks passed")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
