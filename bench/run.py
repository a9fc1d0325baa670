#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data found by name: the cell in
``BENCHMARK.json`` names its configuration (``configs/<name>.json``) and
its traffic mix (``traffic/<name>.json``); each metric is a reader
``metrics/<name>.py``; the device's peaks are ``peaks.json``; the mapping
from compiled programs to layers is ``programs.json``; the limits of the
comparison that decides ``correct`` are ``limits/<cell>.json``.

A run: draw the weights on the devices from the seed; warm every shape
the cell's traffic can reach and then serve ``warmup_s`` of its traffic
(set-up); serve the window; wait for the window's requests; free the
program's state and check the served tokens against the plain float32
reference; print the numbers compared (stderr) and the result line
(last line of stdout).  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

T_PROCESS = time.perf_counter()     # set-up is timed from here

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

DRAIN_S = 60.0          # how long past the window's close answers may take
TRACE_S = 8.0           # seconds of the window the traced run records


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


class BenchError(Exception):
    pass


def load_bench(root: Path):
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell_spec(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise BenchError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_for(bench: dict, workload: str, trace: bool):
    """The cell's metrics for this kind of run, in BENCHMARK.json order."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no reader metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """Backend compilations (and their host time) from JAX's monitoring
    events; persistent-cache hits counted apart."""

    def __init__(self):
        import jax
        self.events = []          # (host time at end, seconds, program)
        self.cache_hits = 0

        def on_duration(event, secs, fun_name="?", **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.events.append((time.perf_counter(), secs, fun_name))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= e[0] < hi for e in self.events)

    def names_between(self, lo: float, hi: float):
        return [e[2] for e in self.events if lo <= e[0] < hi]


class Run:
    """Everything a metric reader may read of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def window_records(self):
        return [r for r in self.records.values()
                if self.w0 <= r.due < self.w1]


def use_persistent_cache(root: Path = ROOT):
    """JAX's compilation cache at one fixed directory in the checkout,
    keeping every program (a run's set-up then loads, never compiles)."""
    cache = root / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def find_devices(chips: int, platform: str = "tpu"):
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise BenchError(f"no accelerator: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def check_peaks(device) -> dict:
    """The device's peaks; a device kind the table lacks is an error."""
    with open(HERE / "peaks.json") as f:
        peaks = json.load(f)["devices"]
    if device.device_kind not in peaks:
        raise BenchError(f"device kind {device.device_kind!r} is not in "
                         "peaks.json")
    return peaks[device.device_kind]


def make_mesh(cfg: dict, devices):
    m = cfg.get("mesh")
    if not m:
        return None
    from repro.launch.mesh import make_serving_mesh
    if len(devices) != m["devices"]:
        raise BenchError(f"mesh wants {m['devices']} devices")
    return make_serving_mesh(m["devices"],
                             model_parallel=m["model_parallel"])


def execute(args, root: Path = ROOT, data: Path = HERE,
            platform: str = "tpu", hooks=None, t_start: float = None) -> dict:
    """One run; returns the result dict (the last stdout line).  Set-up
    is timed from ``t_start`` (default: this module's import)."""
    import arrivals as A
    import check
    import drive
    import system as S
    hooks = hooks or {}
    bench = load_bench(root)
    cell = cell_spec(bench, args.workload)
    cfg = S.load_config(S.config_path(root, bench, cell["config"]))
    mix = A.load_named(data, cell["traffic"])
    use_persistent_cache(root)
    import jax
    devices = find_devices(cell["chips"], platform)
    clock = CompileClock()
    kind = devices[0].device_kind
    peak = check_peaks(devices[0]) if platform == "tpu" else None
    e = cfg["engine"]
    say(f"cell {cell['name']}: {cell['chips']} x {kind}; config "
        f"{cfg['name']}; traffic {cell['traffic']} ({mix['kind']})")
    say(f"slm {cfg['slm']['num_hidden_layers']} layers; llm "
        f"{cfg['llm']['num_hidden_layers']} of "
        f"{cfg['published_llm_layers']} published layers; lane rows "
        f"cloud {e['cloud_rows']} edge {e['edge_rows']}; slm pool "
        f"{e['slm_pool_pages']} x {e['page_size']} tokens per lane, llm "
        f"pool {e['llm_pool_pages']} pages")

    # ---------------------------------------------------------- set-up
    mesh = make_mesh(cfg, devices)
    t0 = time.perf_counter()
    system = S.System(cfg, args.seed, mesh)
    if "after_build" in hooks:
        hooks["after_build"](system)
    say(f"weights drawn and placed in {time.perf_counter() - t0:.3f} s")
    driver = drive.Driver(system, args.seed)
    n0 = len(clock.events)
    warm = drive.warm_shapes(driver, cfg, mix)
    say(f"warmed {warm} shape bursts; {len(clock.events) - n0} compiles")
    plan = drive.Plan(mix, cfg, args.seed, args.seconds)
    w0 = plan.start(driver)
    setup_s = w0 - (T_PROCESS if t_start is None else t_start)
    cold = sum(e[1] for e in clock.events)
    say(f"set-up {setup_s:.3f} s: {len(clock.events)} backend compiles "
        f"({cold:.3f} s), {clock.cache_hits} persistent-cache hits")

    # ---------------------------------------------------------- window
    w1 = w0 + args.seconds
    progs = cap = None
    if args.trace:
        import devtrace
        with open(HERE / "programs.json") as f:
            table = {k: v for k, v in json.load(f).items() if k != "why"}
        progs = devtrace.Programs(system.dep, table)
        cap = drive.TraceWindow(driver, plan.trace_dir(),
                                w0 + max(0.0, (args.seconds - TRACE_S) / 2),
                                TRACE_S)
    plan.window(driver, w1)
    t_closed = time.perf_counter()
    plan.drain(driver, w0, w1, t_closed + DRAIN_S)
    say(f"window closed; drained in {time.perf_counter() - t_closed:.3f} s")
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    lim = (devices[0].memory_stats() or {}).get("bytes_limit")
    say(f"peak_bytes_in_use {mem} of bytes_limit {lim}")

    dops = None
    if args.trace:
        dops = cap.reduce(progs)
        say(f"profiler start took {cap.start_s:.3f} s and stop "
            f"{cap.stop_s:.3f} s, both inside the window")
    run = Run(records=driver.records, boundaries=driver.boundaries,
              w0=w0, w1=w1, seconds=args.seconds, setup_s=setup_s,
              compiles=clock, dops=dops, programs=progs, cfg=cfg, mix=mix,
              peak=peak, chips=len(devices), trace=cap)
    wrec = run.window_records()
    done = [r for r in wrec if r.status == "ok"]
    failed = [r for r in wrec if r.status != "ok"]
    say(f"requests due in the window {len(wrec)}, done {len(done)}, failed "
        f"{len(failed)} (truncated "
        f"{sum(r.status == 'truncated' for r in wrec)}, unfinished "
        f"{sum(r.status == 'pending' for r in wrec)})")
    short = [r for r in done if r.tokens < r.req.max_new]
    say(f"output tokens of the window's requests "
        f"{sum(r.tokens for r in done)}; {len(short)} ended before their "
        f"drawn length")
    say(f"compiles in the window {clock.between(w0, w1)}: "
        f"{clock.names_between(w0, w1)}")
    growth = system.engine.growth_stats()
    say(f"engine growth {growth}")
    metrics = {}
    for m in metrics_for(bench, args.workload, bool(args.trace)):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if "after_metrics" in hooks:
        hooks["after_metrics"](run)
    if dops is not None:
        save_trace_fixture(run, metrics, bench)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    breakdown = None
    if dops is not None:
        device["busy_s"] = dops.busy_s()
        device["window_s"] = dops.window_s()
        breakdown = cap.breakdown(dops, progs)

    # ------------------------------------------------- correctness check
    sample = check.draw_sample(done, args.seed)
    if progs is not None:
        progs.dep = None
    system.close()
    driver.sys = driver.eng = None
    del system
    gc.collect()
    say(f"after freeing the program: bytes_in_use "
        f"{(devices[0].memory_stats() or {}).get('bytes_in_use')}")
    limits = check.load_limits(data, args.workload)
    t_ref = time.perf_counter()
    numbers = check.compare(cfg, args.seed, sample, wrec, limits)
    if "after_check" in hooks:
        hooks["after_check"](cfg, sample, numbers, wrec, limits)
    say(f"reference check over {sum(len(r.token_ids) for r in sample)} "
        f"tokens of {len(sample)} requests in "
        f"{time.perf_counter() - t_ref:.3f} s")
    correct = check.is_correct(numbers)
    for name, n in numbers.items():
        say(f"compared {name}: {n['value']!r} limit {n['limit']!r} "
            f"({'ok' if n['ok'] else 'FAILED'})")
    result = {"correct": correct, "attempted": len(wrec),
              "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": n["value"], "limit": n["limit"]}
                          for k, n in numbers.items()}
    return result


def save_trace_fixture(run: Run, metrics: dict, bench: dict):
    """What the device-trace readers read, and what they read from it:
    ``.bench_trace/fixture.json.gz`` (tests/test_bench_trace.py replays
    a copy of one)."""
    import gzip
    traced = run.trace.boundaries()
    on, off = run.trace.on, run.trace.off
    rids = {e[0] for b in traced for e in b.emitted} | {
        r.req.rid for r in run.records.values()
        if r.admitted is not None and on <= r.admitted <= off}
    names = [m["name"] for m in bench["per_layer"]
             if m["source"] == "device_trace" and m["name"] in metrics]
    import devtrace

    def keep(op):
        return bool(devtrace.KERNEL.search(op.name))
    fx = {"dops": run.dops.compact(keep).to_json(),
          "table": run.programs.table,
          "log": [c for c in run.programs.log
                  if run.trace.on <= c[0] <= run.trace.off],
          "on": run.trace.on, "off": run.trace.off,
          "boundaries": [[b.start, b.end, b.tokens, b.emitted]
                         for b in traced],
          "records": {str(r): [run.records[r].req.prompt_tokens,
                               run.records[r].admitted]
                      for r in rids if r in run.records},
          "cfg": {k: run.cfg[k] for k in ("slm", "llm", "engine")},
          "peak": run.peak, "chips": run.chips,
          "metrics": {n: metrics[n]["value"] for n in names}}
    with gzip.open(Path(run.trace.dir) / "fixture.json.gz", "wt") as f:
        json.dump(fx, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args)
    except BenchError as e:
        say(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
