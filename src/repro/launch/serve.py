"""Serving launcher.

  * --local: run the real hybrid LLM-SLM engine in this process on
    whatever devices JAX finds (reduced configs), with batched requests
    through the scheduler.  Under ``JAX_PLATFORMS=cpu``
    ``--mesh-devices N`` fakes an N-device host mesh (same XLA flag as
    the dry-run) and shards the continuous-decode lanes over it.
    ``chip_smoke.py`` at the repo root serves the pair at published
    widths on a TPU.
  * default: lower the fused co-serving decode step (or a single-arch
    serve step) onto the production mesh.
"""
import os
import sys

from repro.launch.flags import force_host_devices_from_argv

# the device count is locked at first jax init, so both the 512-chip
# dry-run placeholder AND the --local fake host mesh must be set here,
# before any jax import
if "--local" not in sys.argv:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", ""))
else:
    force_host_devices_from_argv(sys.argv)

import argparse  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="single-arch serve step; default: fused pair")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--local", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rtt-ms", type=float, default=50.0)
    ap.add_argument("--timeout-ms", type=float, default=200.0)
    ap.add_argument("--batch", type=int, default=0,
                    help="decode-batch width; >1 uses the continuous-"
                         "batching engine (Pallas-fused logit path)")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="with --local: fake N host devices and lay the "
                         "WHOLE deployment — engine params (SLM, LLM, "
                         "alignment MLP) and decode lanes — over a "
                         "(pod, data, model) serving mesh "
                         "(requires --batch > 1)")
    ap.add_argument("--rules", default="inference",
                    choices=("fsdp", "inference"),
                    help="launch/sharding.py rule set laying the engine "
                         "params over the mesh (inference: weight-"
                         "stationary decode — replicated over data, "
                         "sharded over model)")
    ap.add_argument("--model-parallel", type=int, default=0,
                    help="override the serving mesh's model-axis width "
                         "(must divide --mesh-devices; wider = smaller "
                         "per-device param footprint, less batch "
                         "parallelism)")
    ap.add_argument("--macro-k", type=int, default=8,
                    help="tokens decoded per jitted macro-step dispatch "
                         "(1 host sync per K tokens; 0 = legacy "
                         "per-token step path)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decode window: the SLM drafts K "
                         "tokens greedily, one batched LLM dispatch "
                         "verifies the whole window and rejected "
                         "drafts roll back (0 = off, the per-token "
                         "bit-exact oracle; greedy emits the same "
                         "tokens with ~K-fold fewer LLM round-trips)")
    ap.add_argument("--dense", action="store_true",
                    help="dense stacked lane caches (the paged=False "
                         "bit-exact oracle); default serves paged KV "
                         "with COW shared-prefix admission")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (must divide max_seq)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="page-pool capacity per lane model (0 = size "
                         "for the dense worst case, batch * max_seq)")
    ap.add_argument("--no-lazy-pages", action="store_true",
                    help="reserve every row's worst-case pages at "
                         "admission (the PR 6 policy) instead of lazy "
                         "prompt-pages+1 reservation with growth at "
                         "page boundaries")
    ap.add_argument("--max-ctx", type=int, default=0,
                    help="paged context ceiling in tokens (>= max_seq, "
                         "page-aligned); prompts longer than the dense "
                         "row stream through chunked prefill up to "
                         "this length (0 = max_seq, no long prompts)")
    ap.add_argument("--chunk-width", type=int, default=0,
                    help="dense-buffer width for chunked long-prompt "
                         "prefill (page-aligned, <= max_seq; "
                         "0 = max_seq)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="with --local: per-token cloud-reply loss "
                         "probability, drawn counter-based per "
                         "(rid, step) (0 = fault-free oracle path)")
    ap.add_argument("--outage", default="",
                    help="with --local: periodic cloud-link outage "
                         "windows as PERIOD:LEN in decode steps, e.g. "
                         "32:8 (empty = no outages)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the fault weather (loss draws + "
                         "outage phase)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="with --local: per-request decode deadline in "
                         "simulated ms; expired requests are cancelled "
                         "with partial text (0 = no deadline)")
    ap.add_argument("--sample", action="store_true",
                    help="non-greedy decoding (per-request PRNG keys)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="root seed of the per-request sampling keys")
    ap.add_argument("--adapters", type=int, default=0,
                    help="with --local: register N per-user LoRA "
                         "adapters and spread the demo requests over "
                         "them (requests keep adapter-free rows in the "
                         "mix); requires --adapter-slots")
    ap.add_argument("--adapter-slots", type=int, default=0,
                    help="resident adapter-cache capacity E: the fixed-"
                         "slot device bank mixed per-row into every "
                         "decode dispatch (0 = no adapter serving; "
                         "E < --adapters exercises eviction)")
    ap.add_argument("--adapter-rank", type=int, default=4,
                    help="LoRA rank of the demo adapters (bank slots "
                         "are padded to the model's r_max)")
    from repro.configs.floe_pair import FLOE_PAIRS
    ap.add_argument("--pair", default="2b", choices=sorted(FLOE_PAIRS),
                    help="SLM/LLM pairing; 'gemma3' serves the mixed-"
                         "attention SLM with ring-cached window layers")
    args = ap.parse_args()
    if args.mesh_devices > 1 and not (args.local and args.batch > 1):
        ap.error("--mesh-devices requires --local and --batch > 1 "
                 "(only the continuous-batching lanes are mesh-sharded)")
    if args.model_parallel and args.mesh_devices <= 1:
        ap.error("--model-parallel requires --mesh-devices > 1 (it "
                 "overrides the serving mesh's model-axis width)")
    if args.adapters and not args.adapter_slots:
        ap.error("--adapters requires --adapter-slots > 0 (the "
                 "resident device-bank capacity)")
    if args.adapters and not args.local:
        ap.error("--adapters requires --local (adapter serving runs "
                 "on the real engine, not the dry-run lowering)")
    if args.spec_k and not (args.local and args.batch > 1):
        ap.error("--spec-k requires --local and --batch > 1 (the "
                 "draft/verify burst runs on the batched cloud lane)")

    if args.local:
        import jax
        from repro.configs.floe_pair import needs_ring_cache, pair_configs
        from repro.core import fusion as FUS
        from repro.launch.compile_cache import use_compile_cache
        from repro.models.model import LM
        from repro.serving.deployment import (ServingDeployment,
                                              alignment_shardings,
                                              model_param_shardings)
        from repro.serving.latency import FaultModel, LatencyModel
        from repro.serving.scheduler import (ContinuousBatchScheduler,
                                             Scheduler, summarize)
        use_compile_cache()
        slm_cfg, llm_cfg = pair_configs(args.pair)
        slm = LM(slm_cfg, remat=False,
                 ring_cache=needs_ring_cache(slm_cfg))
        llm = LM(llm_cfg, remat=False)
        mesh = None
        shard = {"slm": None, "llm": None, "mlp": None}
        if args.mesh_devices > 1:
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(args.mesh_devices,
                                     model_parallel=args.model_parallel)
            print(f"serving mesh: {dict(mesh.shape)}")
            shard = {"slm": model_param_shardings(slm, mesh, args.rules),
                     "llm": model_param_shardings(llm, mesh, args.rules),
                     "mlp": alignment_shardings(slm_cfg.vocab_size, mesh,
                                                args.rules)}
        # params are drawn in place: on a mesh every leaf comes out
        # already laid out the way the deployment keeps it
        sp = slm.init(jax.random.key(0), shard["slm"])
        lp = llm.init(jax.random.key(1), shard["llm"])
        mlp = FUS.init_alignment(jax.random.key(2), slm_cfg.vocab_size,
                                 shardings=shard["mlp"])
        fault = None
        if args.fault_rate > 0.0 or args.outage:
            period, olen = 0, 0
            if args.outage:
                period, olen = (int(x) for x in args.outage.split(":"))
            fault = FaultModel(loss_rate=args.fault_rate,
                               outage_period=period, outage_len=olen,
                               seed=args.fault_seed)
            print(f"fault weather: loss_rate={args.fault_rate} "
                  f"outage={args.outage or 'none'} seed={args.fault_seed}")
        # the deployment owns placement: params are laid out over the
        # mesh here, once, and the engines below only do bookkeeping
        dep = ServingDeployment(
            slm, sp, llm, lp, mlp,
            latency=LatencyModel(rtt_ms=args.rtt_ms),
            timeout_ms=args.timeout_ms, sample_seed=args.sample_seed,
            mesh=mesh, rules=args.rules, page_size=args.page_size,
            max_ctx=args.max_ctx or None,
            adapter_slots=args.adapter_slots,
            adapter_rank=args.adapter_rank, fault=fault)
        if mesh is not None:
            pd = dep.per_device_param_bytes()
            print(f"per-device param bytes: {pd['total_bytes']} "
                  f"(replicated would hold {pd['replicated_bytes']})")
        if args.batch > 1:
            kw = dict(batch_size=args.batch, macro_k=args.macro_k,
                      spec_k=args.spec_k, paged=not args.dense,
                      lazy_pages=not args.no_lazy_pages)
            if args.pool_pages:
                kw["pool_pages"] = args.pool_pages
            if args.chunk_width:
                kw["chunk_width"] = args.chunk_width
            sched = ContinuousBatchScheduler.from_deployment(dep, **kw)
            eng = sched.engine
            print(f"lane KV: {'dense' if args.dense else 'paged'}, "
                  f"pool capacity {eng.kv_pool_bytes()}B")
        else:
            sched = Scheduler.from_deployment(dep)
        aids = []
        if args.adapters:
            from repro.core import lora as LORA
            for j in range(args.adapters):
                ad = LORA.init_adapter(slm, jax.random.key(100 + j),
                                       rank=args.adapter_rank,
                                       r_max=dep.adapter_rank)
                sched.engine.adapters.register(f"user{j}", ad)
            print(f"adapters: {args.adapters} registered over "
                  f"{args.adapter_slots} resident slots "
                  f"(rank {args.adapter_rank})")
            # round-robin user ids, one adapter-free row in the mix
            aids = [f"user{j % args.adapters}" for j in range(3)] + [None]
        for i, prompt in enumerate([
            "math: compute 12 plus 7 =",
            "my ssn is 123-45-6789, fill the benefits form",
            "translate to french: water ->",
            "my doctor said my blood pressure is 140 over 90",
        ]):
            sched.submit(prompt, max_new_tokens=8,
                         greedy=not args.sample,
                         adapter_id=aids[i] if aids else None,
                         deadline_ms=args.deadline_ms or None)
        res = sched.run()
        for r in res:
            print(f"[{r.rid}] {r.status.value} private={r.stats.private} "
                  f"cloud={r.stats.cloud_tokens}/{r.stats.tokens} "
                  f"degraded={r.degraded_tokens} lost={r.cloud_lost} "
                  f"lat={r.stats.mean_latency_ms:.0f}ms "
                  f"wait={r.queue_wait_seconds * 1e3:.0f}ms  {r.text!r}")
        print(summarize(res))
        if fault is not None or args.deadline_ms:
            print(f"link health: {sched.engine.health_stats()}")
        if args.adapters:
            print(f"adapter cache: {sched.engine.adapter_stats()}")
        return

    from repro.launch.dryrun import run_fusion, run_one
    if args.arch:
        run_one(args.arch, args.shape, multi_pod=args.multi_pod)
    else:
        run_fusion(args.shape, multi_pod=args.multi_pod)


if __name__ == "__main__":
    main()
